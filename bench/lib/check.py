"""Decide ``correct``: what the timed path served against the reference.

After the measured window has closed, the device's peak memory has been
read and the program's engines are freed, a sample drawn from the seed
is recomputed by the plain float32 reference (``bench/reference``) on
the device, ``BLOCK`` heads at a time:

* stateless heads: ``check`` windows due in the measured window, each
  from rest, no two of them sent the same input;
* stateful heads: ``check`` heads, every window each head was ever
  served (warm-up, window and drain), as one uninterrupted scan;
* fused heads: as stateful, plus the frame wing and the late-logit
  average of the two wings, as served.

The numbers compared (:data:`NAMES`):

* ``spike_count_gap``: for each event-wing layer (conv1, conv2, fc1,
  fc2), the summed |served - reference| spike count over the sample,
  as a share of the reference's summed count; the largest layer's share.
  A served count is the served firing rate x T x neurons;
* ``window_mismatch``: the share of windows (ticks) in which anything
  served differs: any layer's spike count, either wing's logits, the
  actuated logits (a fused head's late-logit average) or the label at
  all, or the PWM by more than float32 rounding (``PWM_ROUNDING``: the
  PWM is recomputed from the tick's logits by a program of its own,
  whose softmax rounds otherwise than the reference's);
* ``label_mismatch``: the share of windows (ticks) whose label differs;
* ``event_logit_gap``: the largest |served - reference| event-wing
  logit;
* ``frame_logit_gap``: the same for the frame wing (fused heads only);
* ``tick_logit_gap``: the same for a fused head's late-logit average
  (read, not held: ``window_mismatch`` counts every tick it moves);
* ``pwm_gap``: the largest |served - reference| PWM duty cycle of the
  actuated result (the fused tick for fused heads);
* ``unserved``: sampled windows with no ``ok`` result.

Each is held to the limit the cell's traffic mix gives it (``limits``):
the sample, and so what a number can read, is the mix's.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

from bench.reference import cutie, scnn

BLOCK = 32
NAMES = ("spike_count_gap", "window_mismatch", "label_mismatch",
         "event_logit_gap", "frame_logit_gap", "tick_logit_gap", "pwm_gap",
         "unserved")
# A duty cycle lies in [0, 1], where float32 spacing is at most 1.2e-7;
# a threshold flip moves it by 0.037 or more (PERF.md).
PWM_ROUNDING = 1e-6


def pick(rec, mix: dict, seed: int) -> List[list]:
    """The sample: a list of heads, each a list of its windows in order."""
    rng = np.random.default_rng(seed + 2)
    if not mix["stateful"]:
        # A stateless window's answer is a function of its input alone,
        # so the sample holds each pool window once: a spike that an
        # input puts on the threshold counts once, however often the
        # traffic sent that input.
        cands = sorted(rec.due_in_window(), key=lambda w: (w.head, w.k))
        distinct = {}
        for i in rng.permutation(len(cands)):
            distinct.setdefault(cands[i].ev, cands[i])
        picked = list(distinct.values())[:mix["check"]]
        return [[w] for w in sorted(picked, key=lambda w: (w.head, w.k))]
    by_head: Dict[int, list] = {}
    for w in rec.windows.values():
        by_head.setdefault(w.head, []).append(w)
    heads = sorted(by_head)
    idx = rng.choice(len(heads), size=min(mix["check"], len(heads)),
                     replace=False)
    return [sorted(by_head[heads[i]], key=lambda w: w.k)
            for i in sorted(idx)]


def _vec(a) -> np.ndarray:
    return np.asarray(a, np.float64).reshape(-1)


def served_row(w, net: dict) -> Optional[dict]:
    """What the program handed back for one window, or None. ``logits``
    are the actuated ones (a fused head's tick), ``ev_logits`` and
    ``fr_logits`` each wing's own."""
    if w.status != "ok" or w.out is None:
        return None
    label, pwm, logits, rates = w.out
    ev_logits = logits
    if w.wings is not None:
        _, _, ev_logits, rates = w.wings["event"]
    rates, sizes = dict(rates), scnn.layer_sizes(net)
    row = {"label": label, "pwm": _vec(pwm), "logits": _vec(logits),
           "ev_logits": _vec(ev_logits),
           "counts": np.array([np.round(rates[n] * net["time_bins"]
                                        * sizes[n]) for n in scnn.LAYERS])}
    if w.wings is not None:
        row["fr_logits"] = _vec(w.wings["frame"][2])
    return row


def _same(g: dict, w: dict) -> bool:
    """Whether a served row equals the reference's in every output, the
    PWM to within float32 rounding."""
    return (g["label"] == w["label"]
            and np.abs(g["pwm"] - w["pwm"]).max() <= PWM_ROUNDING
            and all(np.array_equal(g[n], w[n]) for n in w
                    if n not in ("label", "pwm")))


def _pad_events(pool_events, idx: List[Optional[int]], n: int):
    """(B, n) padded event arrays; ``None`` rows are empty."""
    b = len(idx)
    x, y, t, p = (np.zeros((b, n), np.int32) for _ in range(4))
    valid = np.zeros((b, n), bool)
    for r, i in enumerate(idx):
        if i is None:
            continue
        w = pool_events[i]
        c = w.x.shape[0]
        x[r, :c], y[r, :c], t[r, :c], p[r, :c] = w.x, w.y, w.t, w.p
        valid[r, :c] = True
    return x, y, t, p, valid


def reference_rows(snn_params, tcn_params, pool, sample: List[list],
                   config: dict, precision: str = "highest") -> List[list]:
    """The reference's row for every window of the sample, in the same
    nesting. Heads run ``BLOCK`` at a time, window by window, with the
    membranes carried from one window to the next (a stateless sample
    has one window per head, so each starts from rest)."""
    import jax
    import jax.numpy as jnp
    net, tnet = config["snn"], config.get("tcn")
    window_us = config["window_us"]
    n_ev = 1 << (max(w.x.shape[0] for w in pool.events) - 1).bit_length()
    fused = tnet is not None

    # One program per wing, each the same whatever the cell: a flip of a
    # spike on the threshold must not depend on what else one program
    # holds.
    @jax.jit
    def event(params, x, y, t, p, valid, state):
        vox = scnn.voxelize(x, y, t, p, valid, duration_us=window_us,
                            time_bins=net["time_bins"],
                            height=net["height"], width=net["width"])
        return scnn.forward(params, vox, net, state, precision)

    frame = jax.jit(lambda tparams, pixels: cutie.forward(
        tparams, pixels, tnet, precision))

    @jax.jit
    def actuate(logits):
        return {"label": jnp.argmax(logits, -1), "pwm": scnn.pwm(logits)}

    def run(params, tparams, x, y, t, p, valid, pixels, state):
        out = event(params, x, y, t, p, valid, state)
        row = {"ev_logits": out["logits"], "logits": out["logits"],
               "counts": out["counts"], "state": out["state"]}
        if fused:
            row["fr_logits"] = frame(tparams, pixels)
            row["logits"] = 0.5 * out["logits"] + 0.5 * row["fr_logits"]
        row.update(actuate(row["logits"]))
        return row

    rows: List[list] = [[None] * len(heads) for heads in sample]
    for lo in range(0, len(sample), BLOCK):
        block = sample[lo:lo + BLOCK]
        state = scnn.zero_state(net, BLOCK)
        for k in range(max(len(h) for h in block)):
            wins = [h[k] if k < len(h) else None for h in block]
            wins += [None] * (BLOCK - len(wins))
            ev = _pad_events(pool.events,
                             [None if w is None else w.ev for w in wins],
                             n_ev)
            pixels = np.zeros((BLOCK, 1, 1), np.uint8)
            if fused:
                pixels = np.zeros((BLOCK, tnet["height"], tnet["width"]),
                                  np.uint8)
                for r, w in enumerate(wins):
                    if w is not None:
                        pixels[r] = pool.frames[w.fr].pixels
            out = run(snn_params, tcn_params, *ev, pixels, state)
            state = out.pop("state")
            out = jax.tree_util.tree_map(np.asarray, out)
            for r, w in enumerate(wins[:len(block)]):
                if w is None:
                    continue
                row = {n: out[n][r].astype(np.float64)
                       for n in ("logits", "ev_logits", "pwm", "counts")}
                row["label"] = int(out["label"][r])
                if fused:
                    row["fr_logits"] = out["fr_logits"][r].astype(np.float64)
                rows[lo + r][k] = row
    return rows


def readings(got: List[list], want: List[list]) -> Dict[str, float]:
    """The compared numbers for served (or control) rows against the
    reference's rows. ``frame_logit_gap`` and ``tick_logit_gap`` are None
    without a frame wing."""
    gap = np.zeros(len(scnn.LAYERS))
    total = np.zeros(len(scnn.LAYERS))
    r = {"event_logit_gap": 0.0, "pwm_gap": 0.0, "unserved": 0.0,
         "frame_logit_gap": None, "tick_logit_gap": None}
    mismatched = differ = compared = 0
    for g_head, w_head in zip(got, want):
        for g, w in zip(g_head, w_head):
            if g is None:
                r["unserved"] += 1
                continue
            compared += 1
            gap += np.abs(g["counts"] - w["counts"])
            total += w["counts"]
            mismatched += int(g["label"] != w["label"])
            differ += int(not _same(g, w))
            r["event_logit_gap"] = max(r["event_logit_gap"], float(
                np.abs(g["ev_logits"] - w["ev_logits"]).max()))
            r["pwm_gap"] = max(r["pwm_gap"], float(
                np.abs(g["pwm"] - w["pwm"]).max()))
            if "fr_logits" in w:
                r["frame_logit_gap"] = max(r["frame_logit_gap"] or 0.0, float(
                    np.abs(g["fr_logits"] - w["fr_logits"]).max()))
                r["tick_logit_gap"] = max(r["tick_logit_gap"] or 0.0, float(
                    np.abs(g["logits"] - w["logits"]).max()))
    r["spike_count_gap"] = float(max(
        (g / t for g, t in zip(gap, total) if t > 0), default=0.0))
    r["label_mismatch"] = mismatched / compared if compared else 0.0
    r["window_mismatch"] = differ / compared if compared else 0.0
    return r


def judge(values: Dict[str, Optional[float]],
          limits: Dict[str, float]) -> dict:
    """``{name: {"value", "limit"}}`` for every number that has both a
    reading and a limit, and whether each is within its limit."""
    table = {n: {"value": values[n], "limit": limits[n]} for n in NAMES
             if n in limits and values.get(n) is not None}
    ok = bool(table) and all(v["value"] <= v["limit"]
                             for v in table.values())
    return {"table": table, "ok": ok}


def served_rows(sample: List[list], net: dict) -> List[list]:
    return [[served_row(w, net) for w in head] for head in sample]


def check(rec, mix: dict, config: dict, seed: int, pool, snn_params,
          tcn_params, control: bool = False) -> dict:
    """Sample, run the reference, compare. Returns ``judge``'s dict plus
    every reading and the number of windows compared; with ``control``,
    also the readings of the control (the reference at the precision
    below the configuration's, ``"high"``) on the same sample."""
    sample = pick(rec, mix, seed)
    got = served_rows(sample, config["snn"])
    want = reference_rows(snn_params, tcn_params, pool, sample, config)
    values = readings(got, want)
    out = judge(values, mix["limits"])
    out["readings"] = values
    out["windows"] = sum(len(h) for h in sample)
    if control:
        out["control"] = readings(
            reference_rows(snn_params, tcn_params, pool, sample, config,
                           precision="high"), want)
    return out
