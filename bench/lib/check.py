"""Decide ``correct``: what the timed path served against the reference.

After the measured window has closed, the device's peak memory has been
read and the program's engines are freed, a sample drawn from the seed
is recomputed by the configuration's plain float32 reference
(``bench/reference``, run by its adapter ``bench/arch/<arch>.py``,
``reference_rows``) on the device:

* stateless heads: ``check`` windows due in the measured window, each
  from rest, no two of them sent the same input;
* stateful heads: ``check`` heads, every window each head was ever
  served (warm-up, window and drain), as one uninterrupted scan;
* fused heads: as stateful, plus the frame wing and the late-logit
  average of the two wings, as served.

A row (the adapter's ``served_row`` and ``reference_rows``) holds the
``label``, the actuated ``logits`` and ``pwm``, the event wing's own
``ev_logits``, with a frame wing its ``fr_logits``, and, where the
network reports them, ``counts``: each layer's spike count.

The numbers compared (:data:`NAMES`):

* ``spike_count_gap``: for each layer of ``counts``, the summed
  |served - reference| spike count over the sample, as a share of the
  reference's summed count; the largest layer's share (read only where
  the rows have ``counts``);
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


def _same(g: dict, w: dict) -> bool:
    """Whether a served row equals the reference's in every output, the
    PWM to within float32 rounding."""
    return (g["label"] == w["label"]
            and np.abs(g["pwm"] - w["pwm"]).max() <= PWM_ROUNDING
            and all(np.array_equal(g[n], w[n]) for n in w
                    if n not in ("label", "pwm")))


def readings(got: List[list], want: List[list]) -> Dict[str, float]:
    """The compared numbers for served (or control) rows against the
    reference's rows. ``frame_logit_gap`` and ``tick_logit_gap`` are None
    without a frame wing, ``spike_count_gap`` without ``counts``."""
    gap = total = None
    r = {"event_logit_gap": 0.0, "pwm_gap": 0.0, "unserved": 0.0,
         "frame_logit_gap": None, "tick_logit_gap": None}
    mismatched = differ = compared = 0
    for g_head, w_head in zip(got, want):
        for g, w in zip(g_head, w_head):
            if g is None:
                r["unserved"] += 1
                continue
            compared += 1
            if "counts" in w:
                d = np.abs(g["counts"] - w["counts"])
                gap = d if gap is None else gap + d
                total = w["counts"] if total is None else total + w["counts"]
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
    r["spike_count_gap"] = None if gap is None else float(max(
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


def served_rows(sample: List[list], config: dict, arch) -> List[list]:
    return [[arch.served_row(w, config) for w in head] for head in sample]


def check(rec, mix: dict, config: dict, seed: int, pool, arch, params,
          control: bool = False) -> dict:
    """Sample, run the reference, compare. ``arch`` is the
    configuration's adapter, ``params`` what its ``make_weights`` made.
    Returns ``judge``'s dict plus every reading and the number of
    windows compared; with ``control``, also the readings of the control
    (the reference at the precision below the configuration's,
    ``"high"``) on the same sample."""
    sample = pick(rec, mix, seed)
    got = served_rows(sample, config, arch)
    want = arch.reference_rows(params, pool, sample, config)
    values = readings(got, want)
    out = judge(values, mix["limits"])
    out["readings"] = values
    out["windows"] = sum(len(h) for h in sample)
    if control:
        out["control"] = readings(
            arch.reference_rows(params, pool, sample, config,
                                precision="high"), want)
    return out
