"""A configuration joins the benchmark through its adapter alone.

* A toy network -- a linear classifier over per-pixel event counts, its
  own small engine served through the program's ``StreamEngine`` -- runs
  from files written into a copy of the benchmark (its adapter, its
  plain reference, its configuration, a traffic mix and one more entry
  in ``BENCHMARK.json``), with nothing under ``bench/lib`` changed or
  patched: correct when sound, not correct with its logits perturbed.
* The existing configurations come out bit for bit as they did before
  the adapters: weights, traffic pool, work counts and the reference's
  rows for a fixed sample, at smoke size, against checksums recorded
  from the harness that named the SCNN itself.
"""
import dataclasses
import hashlib
import json
import os
import shutil
import textwrap
import time

import jax
import numpy as np
import pytest

from bench.lib import cells, harness, runner, traffic
from bench.tests import smoke

# -- a network that the harness has never seen ----------------------------

TOY_ADAPTER = '''
"""A linear classifier over each window's per-pixel event counts."""
import os

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib import cells, traffic, weights

REF = cells.load_module(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "reference", "toy.py"))


def sensors(config):
    net = config["net"]
    return {"event": {k: net[k] for k in ("height", "width", "num_classes")}}


def make_weights(seed, config):
    net = config["net"]
    pixels = net["height"] * net["width"]
    w = weights.he_normal(seed, {"toy": (1.0, {
        "w": ((pixels, net["num_classes"]), pixels)})})["toy"]["w"]["w"]
    # Multiples of 1/64: every sum of counts times weights is exact, so
    # the program and the reference agree whatever order they add in.
    return {"w": jnp.round(w * 64.0) / 64.0}


class Engine:
    modality = "event"

    def __init__(self, w, net, offset, duration_us):
        self.w, self.net, self.offset = w, net, offset
        self.duration_us = duration_us
        self._fn = jax.jit(self._logits)

    def _logits(self, w, x, y, valid):
        n = self.net["height"] * self.net["width"]
        counts = jax.vmap(lambda i, v: jnp.zeros(n, jnp.float32).at[i].add(
            v.astype(jnp.float32)))(y * self.net["width"] + x, valid)
        return jnp.matmul(counts, w, precision=jax.lax.Precision.HIGHEST) \\
            + self.offset

    def validate(self, item):
        if item.duration_us != self.duration_us:
            raise ValueError("one window length per engine")

    def prepare(self, items, *, batch_size):
        from repro.core import events as ev
        bucket = ev.next_pow2(max(
            (w.num_events for w in items if w is not None), default=1))
        return ev.pad_event_windows(items, max_events=bucket,
                                    batch_size=batch_size,
                                    duration_us=self.duration_us)

    def shape_key(self, batch):
        return (batch.batch_size, batch.max_events, batch.duration_us)

    def init_state(self, batch_size):
        return {}

    def warmup(self, keys):
        for b, n, _ in keys:
            z = np.zeros((b, n), np.int32)
            self._fn(self.w, z, z, z.astype(bool)).block_until_ready()

    def infer(self, batch, state=None):
        from repro.core.pipeline import ClosedLoopResult, pwm_from_logits
        logits = np.asarray(self._fn(self.w, batch.x, batch.y, batch.valid))
        pwm = np.asarray(pwm_from_logits(logits))
        out = [None if not batch.occupied[b] else ClosedLoopResult(
            label_pred=np.argmax(logits[b:b + 1], -1), pwm=pwm[b:b + 1],
            latency_ms=0.0, energy_mj=0.0, breakdown={}, realtime=True,
            sustained_rate_hz=0.0, logits=logits[b:b + 1])
            for b in range(batch.batch_size)]
        return out if state is None else (out, state)


def build(config, params, engine_config):
    return [Engine(params["w"], config["net"], config["logit_offset"],
                   engine_config.duration_us)]


def shape_keys(config, slots, pool, window_us):
    from repro.core import events as ev
    n = ev.next_pow2(max(w.x.shape[0] for w in pool.events))
    return {"event": (slots, n, window_us)}


def served_row(w, config):
    if w.status != "ok" or w.out is None:
        return None
    label, pwm, logits, _ = w.out
    v = lambda a: np.asarray(a, np.float64).reshape(-1)
    return {"label": label, "pwm": v(pwm), "logits": v(logits),
            "ev_logits": v(logits)}


def reference_rows(params, pool, sample, config, precision="highest"):
    rows = []
    for head in sample:
        x, y, _, _, valid = traffic.pad_events(
            pool.events, [w.ev for w in head],
            max(w.x.shape[0] for w in pool.events))
        out = REF.forward(np.asarray(params["w"]), x, y, valid,
                          config["net"], precision)
        rows.append([{"label": int(out["label"][k]), "pwm": out["pwm"][k],
                      "logits": out["logits"][k],
                      "ev_logits": out["logits"][k]}
                     for k in range(len(head))])
    return rows


def window_flops(config):
    net = config["net"]
    return 2.0 * net["height"] * net["width"] * net["num_classes"]


def kernel_work(config, slots):
    return {}
'''

TOY_REFERENCE = '''
"""Plain reference of the toy network: count each pixel's events, then
one matmul in float64."""
import numpy as np

from bench.reference import scnn


def forward(w, x, y, valid, net, precision="highest"):
    counts = np.zeros((x.shape[0], net["height"] * net["width"]))
    for r in range(x.shape[0]):
        np.add.at(counts[r], (y[r] * net["width"] + x[r])[valid[r]], 1.0)
    logits = counts @ np.asarray(w, np.float64)
    return {"logits": logits, "label": logits.argmax(-1),
            "pwm": np.asarray(scnn.pwm(logits.astype(np.float32)),
                              np.float64)}
'''

TOY_CONFIG = {"name": "toy_linear", "arch": "toy",
              "net": {"height": 16, "width": 16, "num_classes": 5},
              "window_us": 300000, "slots_per_chip": 4, "pipeline_depth": 1,
              "logit_offset": 0.0}

TOY_TRAFFIC = {"why": "closed loop over a toy network", "loop": "closed",
               "heads": 2, "queued_per_head": 2, "stateful": False,
               "fusion": False, "mean_events": 600, "pool_windows": 8,
               "warm_s": 0.3, "check": 8,
               "limits": {"window_mismatch": 0.0, "event_logit_gap": 1e-6,
                          "unserved": 0}}


def _write(path, text):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write(text)


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    """A copy of the benchmark plus the toy network's files only."""
    root = str(tmp_path_factory.mktemp("checkout"))
    shutil.copytree(os.path.join(cells.ROOT, "bench"),
                    os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    spec = cells.load_spec()
    spec["configs"].append({
        "name": "toy_linear", "source": "a test of the adapter seam",
        "file": "bench/configs/toy_linear.json", "reduced": [],
        "why": "a linear classifier over per-pixel event counts"})
    spec["workloads"].append({
        "name": "toy_closed", "config": "toy_linear",
        "traffic": "toy_closed", "chips": 1,
        "why": "two closed-loop heads over the toy network"})
    _write(os.path.join(root, "BENCHMARK.json"), json.dumps(spec, indent=1))
    _write(os.path.join(root, "bench", "arch", "toy.py"),
           textwrap.dedent(TOY_ADAPTER))
    _write(os.path.join(root, "bench", "reference", "toy.py"),
           textwrap.dedent(TOY_REFERENCE))
    _write(os.path.join(root, "bench", "configs", "toy_linear.json"),
           json.dumps(TOY_CONFIG))
    _write(os.path.join(root, "bench", "traffic", "toy_closed.json"),
           json.dumps(TOY_TRAFFIC))
    return root


def _toy_run(root, **config):
    cell = cells.cell("toy_closed", root=root)
    cell = dataclasses.replace(cell, config=dict(cell.config, **config))
    return runner.run_cell(cell, 2 ** 31 + 4242, 1.0, False,
                           time.perf_counter(), chip=False)


def test_a_new_network_runs_from_new_files_alone(toy_root):
    out = _toy_run(toy_root)
    assert out["correct"], out["compared"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"setup_s"}
    assert out["compared"]["event_logit_gap"]["value"] == 0.0


def test_a_new_network_with_altered_logits_is_not_correct(toy_root):
    out = _toy_run(toy_root, logit_offset=0.25)
    assert out["correct"] is False
    assert out["compared"]["event_logit_gap"]["value"] == pytest.approx(0.25)
    assert out["compared"]["window_mismatch"]["value"] == 1.0


# -- the existing configurations, bit for bit ------------------------------

SEED = 2 ** 31 + 1234
# Recorded at smoke size (bench/tests/smoke.py) with the harness before
# the adapters, which made weights in bench/lib/weights.py, counted work
# in bench/lib/work.py and ran the reference from bench/lib/check.py.
PINNED = {
    "scnn_paper_saturated": {
        "sample": (5, 1),
        "weights": "9015deb18af94f7975789c186ad91e87"
                   "908fd1497b6c12a753b701f49b50534e",
        "pool": "fe4a562c0d95ae0bdffdaa26efbfd967"
                "239819c19e00eed3a736e3e047a13b95",
        "reference_rows": "fd2190ec14c50c2ba28f4fe00ae4354e"
                          "0e42a9345552a388e8b1dbc426e13468",
        "window_flops": (169472.0, 80920576.0)},
    "fusion_uav_p80": {
        "sample": (3, 2),
        "weights": "d39e86b57899fd4b3a6d6515d7658cb9"
                   "a344d567779dc9c92158238f12d9f802",
        "pool": "34114666594d87e292ebc64946018ead"
                "6717c4a952f5a51e9a6936ab27ef9f4d",
        "reference_rows": "f158eaddfe6996fec07ec4fcf052deec"
                          "276f3e640ac4c5870a0748935ecfc49b",
        "window_flops": (186048.0, 85683200.0)},
}
# The SCNN's kernel calls per step, at smoke size (4 slots) and at
# Table II widths (32 slots); the same for both configurations.
KERNEL_WORK = {
    4: {"fc_lif_scan": [{"ops": 68608, "read": 8704, "write": 4608},
                        {"ops": 23584, "read": 5680, "write": 1584}],
        "lif_scan": [{"ops": 24576, "read": 36864, "write": 36864},
                     {"ops": 12288, "read": 18432, "write": 18432}]},
    32: {"fc_lif_scan": [
            {"ops": 1074528256, "read": 8454144, "write": 1114112},
            {"ops": 5784064, "read": 1072512, "write": 23936}],
         "lif_scan": [
            {"ops": 25165824, "read": 35651584, "write": 35651584},
            {"ops": 12582912, "read": 17825792, "write": 17825792}]},
}


def _digest(arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        a = np.ascontiguousarray(np.asarray(a))
        h.update(str(a.dtype).encode() + str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


def _pool_arrays(pool):
    out = []
    for w in pool.events:
        out += [w.x, w.y, w.t, w.p, np.int64(w.label),
                np.int64(w.duration_us)]
    for f in pool.frames:
        out += [f.pixels, np.int64(f.label)]
    return out


def _sample(pool, heads, windows):
    return [[harness.Window(head=h, k=k, due=0.0,
                            ev=(7 * h + k) % len(pool.events),
                            fr=(3 * h + k) % len(pool.frames)
                            if pool.frames else None)
             for k in range(windows)] for h in range(heads)]


@pytest.mark.parametrize("name", sorted(PINNED))
def test_existing_configurations_are_unchanged_bit_for_bit(name):
    pin = PINNED[name]
    cell = smoke.cell(name)
    config, arch = cell.config, cell.arch
    params = arch.make_weights(SEED, config)
    assert _digest(jax.tree_util.tree_leaves(params)) == pin["weights"]
    pool = traffic.make_pool(SEED, cell.mix, arch.sensors(config),
                             config["window_us"])
    assert _digest(_pool_arrays(pool)) == pin["pool"]
    full = cells.cell(name).config
    assert (arch.window_flops(config), arch.window_flops(full)) \
        == pin["window_flops"]
    assert arch.kernel_work(config, config["slots_per_chip"]) \
        == KERNEL_WORK[4]
    assert arch.kernel_work(full, 32) == KERNEL_WORK[32]
    rows = arch.reference_rows(params, pool, _sample(pool, *pin["sample"]),
                               config)
    assert _digest(np.asarray(r[k], np.float64) for head in rows
                   for r in head for k in sorted(r)) == pin["reference_rows"]
