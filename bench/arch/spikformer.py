"""The adapter of Spikformer on DVS windows (``"arch": "spikformer"``).

The functions the harness calls (``bench/lib/cells.py``): ``sensors``,
``make_weights``, ``build``, ``shape_keys``, ``served_row``,
``reference_rows``, ``window_flops`` and ``kernel_work``.

Configuration keys read here: ``spikformer`` (the network's sizes, LIF
constants and ``init_gain``: ``sps`` for the stem's convolutions -- the
SPS stages and RPE --, ``blocks`` for the token linears and the head),
``window_us``.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

from bench.lib import traffic, weights, work
from bench.reference import spikformer as ref
# Imported as the adapter loads: a program without Spikformer fails the
# cell before any set-up.
from repro.core.spikformer import SpikformerConfig

BLOCK = 32      # heads the reference runs at a time


def sensors(config: dict) -> dict:
    net = config["spikformer"]
    return {"event": {k: net[k] for k in ("height", "width", "num_classes")}}


def _check(net: dict) -> None:
    if net["sps_channels"][-1] != net["embed_dim"]:
        raise ValueError(f"the last SPS stage ({net['sps_channels'][-1]}) "
                         f"must give embed_dim ({net['embed_dim']})")


def _params(net: dict) -> Dict[str, tuple]:
    """Each layer's (weight shape, fan-in, has a bias, has batch norm);
    convs HWIO, linears (K, N)."""
    _check(net)
    out, c_in = {}, net["in_channels"]
    for i, c in enumerate(net["sps_channels"]):
        out[f"sps{i}"] = ((3, 3, c_in, c), 9 * c_in, False, True)
        c_in = c
    d, hidden = net["embed_dim"], net["embed_dim"] * net["mlp_ratio"]
    out["rpe"] = ((3, 3, d, d), 9 * d, False, True)
    for j in range(net["depth"]):
        p = f"block{j}."
        for n in ("q", "k", "v"):
            out[p + n] = ((d, d), d, False, True)
        out[p + "proj"] = ((d, d), d, True, True)
        out[p + "mlp1"] = ((d, hidden), d, True, True)
        out[p + "mlp2"] = ((hidden, d), hidden, True, True)
    out["head"] = ((d, net["num_classes"]), d, True, False)
    return out


def make_weights(seed: int, config: dict) -> dict:
    """Float32, in one jitted call: He-normal weights times the gain of
    their part (``init_gain``), PyTorch's uniform(+-1/sqrt(fan_in))
    biases, batch norm at its initial affine (scale 1/sqrt(1 + eps),
    shift 0)."""
    import jax
    import jax.numpy as jnp
    net = config["spikformer"]
    layers = _params(net)
    gains, eps = net["init_gain"], net["bn_eps"]

    def init(key):
        out = {}
        for (name, (shape, fan_in, bias, bn)), k in zip(
                layers.items(), jax.random.split(key, len(layers))):
            kw, kb = jax.random.split(k)
            n = shape[-1]
            stem = name.startswith("sps") or name == "rpe"
            gain = gains["sps" if stem else "blocks"]
            p = {"w": jax.random.normal(kw, shape, jnp.float32)
                 * (gain * (2.0 / fan_in) ** 0.5)}
            if bias:
                p["b"] = jax.random.uniform(kb, (n,), jnp.float32,
                                            -fan_in ** -0.5, fan_in ** -0.5)
            if bn:
                p["bn_scale"] = jnp.full((n,), (1.0 + eps) ** -0.5,
                                         jnp.float32)
                p["bn_shift"] = jnp.zeros((n,), jnp.float32)
            out[name] = p
        return out

    return jax.jit(init)(weights.key(seed))


def program_config(net: dict) -> SpikformerConfig:
    _check(net)
    return SpikformerConfig(
        height=net["height"], width=net["width"],
        in_channels=net["in_channels"],
        sps_channels=tuple(net["sps_channels"]),
        num_heads=net["num_heads"], mlp_ratio=net["mlp_ratio"],
        depth=net["depth"], num_classes=net["num_classes"],
        time_bins=net["time_bins"], tau=net["tau"], v_th=net["v_th"],
        attn_v_th=net["attn_v_th"], attn_scale=net["attn_scale"],
        bn_eps=net["bn_eps"])


def build(config: dict, params: dict, engine_config) -> list:
    """``BatchedClosedLoop`` over Spikformer with the ``lif_scan`` kernel
    (and ``fc_lif_scan`` for the token linears, ``fuse_fc``)."""
    from repro.core import BatchedClosedLoop
    from repro.kernels import lif_scan
    return [BatchedClosedLoop.from_config(
        params, program_config(config["spikformer"]), engine_config,
        lif_scan_fn=lif_scan)]


def shape_keys(config: dict, slots: int, pool: traffic.Pool,
               window_us: int) -> Dict[str, tuple]:
    """One event key at the pool's event bucket (``next_pow2``)."""
    from repro.core import events as ev
    return {"event": (slots, ev.next_pow2(max(w.x.shape[0]
                                              for w in pool.events)),
                      window_us)}


def _vec(a) -> np.ndarray:
    return np.asarray(a, np.float64).reshape(-1)


def served_row(w, config: dict) -> Optional[dict]:
    """What the program handed back for one window, or None. ``counts``
    are each LIF layer's spikes: the served firing rate x T x neurons,
    exact, as every layer's T x neurons is a power of two."""
    if w.status != "ok" or w.out is None:
        return None
    net = config["spikformer"]
    label, pwm, logits, rates = w.out
    rates = dict(rates)
    counts = [np.round(rates[n] * net["time_bins"] * int(np.prod(s)))
              for n, s in ref.layers(net).items()]
    return {"label": label, "pwm": _vec(pwm), "logits": _vec(logits),
            "ev_logits": _vec(logits), "counts": np.array(counts)}


def reference_rows(params: dict, pool: traffic.Pool, sample: List[list],
                   config: dict, precision: str = "highest") -> List[list]:
    """The reference's row for every window of the sample, in the same
    nesting: heads ``BLOCK`` at a time, window by window, membranes
    carried from one window of a head to the next."""
    import jax
    net, window_us = config["spikformer"], config["window_us"]
    n_ev = 1 << (max(w.x.shape[0] for w in pool.events) - 1).bit_length()

    @jax.jit
    def run(params, x, y, t, p, valid, state):
        vox = ref.voxelize(x, y, t, p, valid, duration_us=window_us,
                           time_bins=net["time_bins"], height=net["height"],
                           width=net["width"])
        return ref.forward(params, vox, net, state, precision)

    rows: List[list] = [[None] * len(heads) for heads in sample]
    for lo in range(0, len(sample), BLOCK):
        block = sample[lo:lo + BLOCK]
        state = ref.zero_state(net, BLOCK)
        for k in range(max(len(h) for h in block)):
            wins = [h[k] if k < len(h) else None for h in block]
            wins += [None] * (BLOCK - len(wins))
            ev = traffic.pad_events(
                pool.events, [None if w is None else w.ev for w in wins],
                n_ev)
            out = run(params, *ev, state)
            state = out.pop("state")
            out = jax.tree_util.tree_map(np.asarray, out)
            for r, w in enumerate(wins[:len(block)]):
                if w is None:
                    continue
                row = {n: out[n][r].astype(np.float64)
                       for n in ("logits", "pwm", "counts")}
                row["ev_logits"] = row["logits"]
                row["label"] = int(out["label"][r])
                rows[lo + r][k] = row
    return rows


# -- work ------------------------------------------------------------------

def _token_linears(net: dict) -> List[tuple]:
    """Each fused token-linear call of one block: (K, N)."""
    d, hidden = net["embed_dim"], net["embed_dim"] * net["mlp_ratio"]
    return [(d, 3 * d), (d, d), (d, hidden), (hidden, d)]  # qkv, proj, mlp


def kernel_work(config: dict, slots: int) -> Dict[str, List[Dict[str, float]]]:
    """Per step of ``slots`` slots: each call of the served Pallas kernels.
    ``lif_scan``: the four SPS stages, RPE and each block's attention LIF;
    ``fc_lif_scan``: each block's q/k/v (one call), proj and both MLP
    layers over slots x tokens rows, each with its bias (one add per
    current, the bias row read once)."""
    net = config["spikformer"]
    t = net["time_bins"]
    shapes = ref.layers(net)
    n_tok = int(np.prod(shapes["rpe"][:2]))
    scans = ["sps0", "sps1", "sps2", "sps3", "rpe"] + [
        f"block{j}.attn" for j in range(net["depth"])]
    fc = []
    for _ in range(net["depth"]):
        for k, n in _token_linears(net):
            w = work.fc_lif_scan(t, slots * n_tok, k, n)
            w["ops"] += t * slots * n_tok * n
            w["read"] += work.F32 * n
            fc.append(w)
    return {"lif_scan": [work.lif_scan(t, slots, int(np.prod(shapes[s])))
                         for s in scans],
            "fc_lif_scan": fc}


def window_flops(config: dict) -> float:
    """Model FLOPs of one window, dense: the convolutions, the token
    linears and the attention products over T steps, then the head."""
    net = config["spikformer"]
    shapes = ref.layers(net)
    c_in, conv = net["in_channels"], 0
    for i, c in enumerate(net["sps_channels"]):
        h, w, _ = shapes[f"sps{i}"]
        conv += 2 * h * w * c * 9 * c_in
        c_in = c
    h, w, d = shapes["rpe"]
    n_tok = h * w
    conv += 2 * n_tok * d * 9 * d
    block = sum(2 * n_tok * k * n for k, n in _token_linears(net))
    block += 2 * (2 * n_tok * n_tok * d)        # Q K^T, then (Q K^T) V
    head = 2 * d * net["num_classes"]
    return float(net["time_bins"] * (conv + net["depth"] * block) + head)
