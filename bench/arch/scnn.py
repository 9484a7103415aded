"""The adapter of the Table II SCNN, with the CUTIE wing when the
configuration has one (``"arch": "scnn"``).

A configuration names its adapter with ``"arch"``; the harness finds
``bench/arch/<arch>.py`` by that name and calls only these functions:

* :func:`sensors`: what the traffic generator makes for the cell;
* :func:`make_weights`: the parameters, on the device, from the seed;
* :func:`build`: the program's engines, one per wing, for ``StreamEngine``;
* :func:`shape_keys`: the executables the cell's traffic uses;
* :func:`served_row` and :func:`reference_rows`: what the check compares;
* :func:`window_flops` and :func:`kernel_work`: the work of a window and
  of each Pallas kernel call, for ``step_mfu`` and the roofline readers.

Configuration keys read here: ``snn`` (the event wing's sizes), ``tcn``
(the frame wing's, optional), ``window_us``.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

from bench.lib import traffic, weights, work
from bench.reference import cutie, scnn

BLOCK = 32      # heads the reference runs at a time


# -- sensors and weights ---------------------------------------------------

def sensors(config: dict) -> dict:
    """Event geometry and classes; frame geometry with a frame wing."""
    out = {"event": {k: config["snn"][k]
                     for k in ("height", "width", "num_classes")}}
    if "tcn" in config:
        out["frame"] = {k: config["tcn"][k]
                        for k in ("height", "width", "num_classes")}
    return out


def _sizes(net: dict):
    h0, w0 = net["height"] // net["pool0"], net["width"] // net["pool0"]
    return h0, w0, (h0 // 4) * (w0 // 4) * net["conv2_features"]


def _layers(net: dict) -> dict:
    """Each layer's weight shape (conv kernels HWIO) and fan-in."""
    flat = _sizes(net)[2]
    return {
        "conv1": ((3, 3, net["in_channels"], net["conv1_features"]),
                  9 * net["in_channels"]),
        "conv2": ((3, 3, net["conv1_features"], net["conv2_features"]),
                  9 * net["conv1_features"]),
        "fc1": ((flat, net["hidden"]), flat),
        "fc2": ((net["hidden"], net["num_classes"]), net["hidden"]),
    }


def make_weights(seed: int, config: dict) -> dict:
    """``{"snn": ..., "tcn": ...}`` (``tcn`` with a frame wing), float32
    and He-initialised with each network's ``init_gain``. The CUTIE
    weights are made in float32; the program ternarizes and packs them
    itself, and the reference ternarizes its own copy."""
    nets = {name: (config[name]["init_gain"], _layers(config[name]))
            for name in ("snn", "tcn") if name in config}
    return weights.he_normal(seed, nets)


# -- the program -------------------------------------------------------------

def snn_config(net: dict):
    from repro.core import SNNConfig
    from repro.core.lif import LIFParams
    return SNNConfig(
        height=net["height"], width=net["width"],
        in_channels=net["in_channels"], pool0=net["pool0"],
        conv1_features=net["conv1_features"],
        conv2_features=net["conv2_features"], hidden=net["hidden"],
        num_classes=net["num_classes"], time_bins=net["time_bins"],
        lif=LIFParams(alpha=net["lif_alpha"], v_th=net["lif_v_th"]))


def tcn_config(net: dict):
    from repro.core import TCNConfig
    return TCNConfig(
        height=net["height"], width=net["width"],
        in_channels=net["in_channels"], pool0=net["pool0"],
        conv1_features=net["conv1_features"],
        conv2_features=net["conv2_features"], hidden=net["hidden"],
        num_classes=net["num_classes"], act_threshold=net["act_threshold"])


def build(config: dict, params: dict, engine_config) -> list:
    """``BatchedClosedLoop`` with the ``lif_scan`` kernel, and
    ``FrameTCNEngine`` with a frame wing."""
    from repro.core import BatchedClosedLoop, FrameTCNEngine
    from repro.kernels import lif_scan
    engines = [BatchedClosedLoop.from_config(
        params["snn"], snn_config(config["snn"]), engine_config,
        lif_scan_fn=lif_scan)]
    if "tcn" in config:
        engines.append(FrameTCNEngine.from_config(
            params["tcn"], tcn_config(config["tcn"]), engine_config))
    return engines


def shape_keys(config: dict, slots: int, pool: traffic.Pool,
               window_us: int) -> Dict[str, tuple]:
    """One event key at the pool's event bucket (the program's rule,
    ``next_pow2``), and the frame key with a frame wing."""
    from repro.core import events as ev
    keys = {"event": (slots, ev.next_pow2(max(w.x.shape[0]
                                              for w in pool.events)),
                      window_us)}
    if "tcn" in config:
        tcn = config["tcn"]
        keys["frame"] = (slots, tcn["height"], tcn["width"], window_us)
    return keys


# -- what the check compares -----------------------------------------------

def _vec(a) -> np.ndarray:
    return np.asarray(a, np.float64).reshape(-1)


def served_row(w, config: dict) -> Optional[dict]:
    """What the program handed back for one window, or None. ``logits``
    are the actuated ones (a fused head's tick), ``ev_logits`` and
    ``fr_logits`` each wing's own, ``counts`` each SCNN layer's spike
    count (the served firing rate x T x neurons)."""
    if w.status != "ok" or w.out is None:
        return None
    net = config["snn"]
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


def reference_rows(params: dict, pool: traffic.Pool, sample: List[list],
                   config: dict, precision: str = "highest") -> List[list]:
    """The reference's row for every window of the sample, in the same
    nesting. Heads run ``BLOCK`` at a time, window by window, with the
    membranes carried from one window to the next (a stateless sample
    has one window per head, so each starts from rest)."""
    import jax
    import jax.numpy as jnp
    net, tnet = config["snn"], config.get("tcn")
    snn_params, tcn_params = params["snn"], params.get("tcn")
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
            ev = traffic.pad_events(
                pool.events, [None if w is None else w.ev for w in wins],
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


# -- work ------------------------------------------------------------------

def kernel_work(config: dict, slots: int) -> Dict[str, List[Dict[str, float]]]:
    """Per step of ``slots`` slots: each call of the served event wing's
    Pallas kernels, by kernel (conv1 and conv2 through ``lif_scan``,
    fc1 and fc2 through ``fc_lif_scan``)."""
    net = config["snn"]
    t = net["time_bins"]
    h0, w0, flat = _sizes(net)
    return {
        "lif_scan": [
            work.lif_scan(t, slots, h0 * w0 * net["conv1_features"]),
            work.lif_scan(t, slots, (h0 // 2) * (w0 // 2)
                          * net["conv2_features"])],
        "fc_lif_scan": [
            work.fc_lif_scan(t, slots, flat, net["hidden"]),
            work.fc_lif_scan(t, slots, net["hidden"], net["num_classes"])]}


def snn_flops(net: dict) -> float:
    """Model FLOPs of one event window: the SCNN's convolutions and fully
    connected layers, dense, over T steps."""
    t = net["time_bins"]
    h0, w0, flat = _sizes(net)
    conv1 = 2 * h0 * w0 * net["conv1_features"] * 9 * net["in_channels"]
    conv2 = (2 * (h0 // 2) * (w0 // 2) * net["conv2_features"] * 9
             * net["conv1_features"])
    fc = 2 * flat * net["hidden"] + 2 * net["hidden"] * net["num_classes"]
    return float(t * (conv1 + conv2 + fc))


def tcn_flops(net: dict) -> float:
    """Model FLOPs of one frame through the CUTIE network."""
    h0, w0, flat = _sizes(net)
    conv1 = 2 * h0 * w0 * net["conv1_features"] * 9 * net["in_channels"]
    conv2 = (2 * (h0 // 2) * (w0 // 2) * net["conv2_features"] * 9
             * net["conv1_features"])
    return float(conv1 + conv2 + 2 * flat * net["hidden"]
                 + 2 * net["hidden"] * net["num_classes"])


def window_flops(config: dict) -> float:
    """Model FLOPs of one served window (a fused tick: both wings)."""
    f = snn_flops(config["snn"])
    if "tcn" in config:
        f += tcn_flops(config["tcn"])
    return f
