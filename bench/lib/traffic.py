"""The one traffic generator: sensor windows and their schedule.

A traffic mix is a JSON file under ``bench/traffic/`` (see
:func:`load`). Its keys:

* ``loop``: ``"closed"`` -- every head keeps ``queued_per_head``
  windows queued and submits its next one when a result comes back --
  or ``"open"`` -- every head submits one window per ``period_ms``,
  whatever the server does.
* ``heads``: heads per chip (the cell's chip count multiplies it).
* ``stateful``: heads carry their membranes across windows.
* ``fusion``: each head is an event + frame ``FusionSession``.
* ``mean_events``: Poisson mean of DVS events per 300 ms window.
* ``pool_windows`` / ``pool_frames``: how many distinct windows and
  frames are made at set-up and cycled through.
* ``warm_s``: seconds of traffic served before the measured window.
* ``check``: how many windows (stateless heads) or heads (stateful
  heads, every window of each) the correctness check compares;
* ``limits``: the limit of each number the check compares on that
  sample (``bench/lib/check.py``), set from readings on the chip
  (PERF.md).

The event and frame generators are copies of the program's seeded
synthetic DVS-Gesture generators (``repro.core.events`` and
``repro.core.frames``), kept here so that the yardstick cannot move
with the program.
"""
from __future__ import annotations

import dataclasses
import json
import os
from typing import List, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
TRAFFIC_DIR = os.path.join(os.path.dirname(HERE), "traffic")

KEYS = {"loop", "heads", "queued_per_head", "period_ms", "stateful",
        "fusion", "mean_events", "pool_windows", "pool_frames", "warm_s",
        "check", "limits", "why"}


def load(name: str, path: Optional[str] = None) -> dict:
    """The traffic mix ``bench/traffic/<name>.json``, checked."""
    path = path or os.path.join(TRAFFIC_DIR, f"{name}.json")
    with open(path) as f:
        mix = json.load(f)
    unknown = set(mix) - KEYS
    if unknown:
        raise ValueError(f"traffic {name}: unknown keys {sorted(unknown)}")
    if mix["loop"] not in ("open", "closed"):
        raise ValueError(f"traffic {name}: loop must be open or closed")
    mix.setdefault("queued_per_head", 2)
    mix.setdefault("period_ms", 300.0)
    mix.setdefault("stateful", False)
    mix.setdefault("fusion", False)
    mix.setdefault("pool_frames", 0)
    return mix


# ----------------------------------------------------------------------
# Windows (copied generators)
# ----------------------------------------------------------------------

@dataclasses.dataclass
class Events:
    """One window of DVS events; the program's ``EventWindow`` fields."""
    x: np.ndarray
    y: np.ndarray
    t: np.ndarray
    p: np.ndarray
    duration_us: int
    label: int


@dataclasses.dataclass
class Frame:
    """One grayscale camera frame; the program's ``FrameWindow`` fields."""
    pixels: np.ndarray
    duration_us: int
    label: int


def gesture_events(rng: np.random.Generator, label: int, *,
                   duration_us: int, mean_events: int, height: int,
                   width: int, num_classes: int) -> Events:
    """A DVS-Gesture-like window: a class-dependent orbit of an edge
    cluster plus 10% uniform noise, Poisson(``mean_events``) events."""
    n = max(int(rng.poisson(mean_events)), 1024)
    w0 = 2.0 * np.pi * (1.0 + 0.7 * label)
    radius = 20.0 + 3.0 * (label % 4)
    cx = width / 2.0 + 12.0 * np.cos(2.0 * np.pi * label / num_classes)
    cy = height / 2.0 + 12.0 * np.sin(2.0 * np.pi * label / num_classes)
    phase = 2.0 * np.pi * label / num_classes
    vertical = label % 2 == 0
    t = np.sort(rng.integers(0, duration_us, size=n)).astype(np.int64)
    ang = w0 * (t.astype(np.float64) / duration_us) + phase
    px = cx + radius * np.cos(ang)
    py = cy + radius * (np.sin(2 * ang) if vertical else np.sin(ang))
    x = np.clip(np.round(px + rng.normal(0.0, 3.0, size=n)), 0, width - 1)
    y = np.clip(np.round(py + rng.normal(0.0, 3.0, size=n)), 0, height - 1)
    p = ((np.cos(ang) + rng.normal(0, 0.35, size=n)) > 0).astype(np.int32)
    noise = rng.random(n) < 0.10
    x = np.where(noise, rng.integers(0, width, size=n), x).astype(np.int32)
    y = np.where(noise, rng.integers(0, height, size=n), y).astype(np.int32)
    p = np.where(noise, rng.integers(0, 2, size=n), p).astype(np.int32)
    return Events(x=x, y=y, t=t.astype(np.int32), p=p,
                  duration_us=duration_us, label=label)


def gesture_frame(rng: np.random.Generator, label: int, *, duration_us: int,
                  height: int, width: int, num_classes: int,
                  exposure_steps: int = 24) -> Frame:
    """A frame of the same gesture family: the orbit's motion-blurred
    trail over a noisy background, uint8."""
    w0 = 2.0 * np.pi * (1.0 + 0.7 * label)
    radius = 20.0 + 3.0 * (label % 4)
    cx = width / 2.0 + 12.0 * np.cos(2.0 * np.pi * label / num_classes)
    cy = height / 2.0 + 12.0 * np.sin(2.0 * np.pi * label / num_classes)
    phase = 2.0 * np.pi * label / num_classes
    vertical = label % 2 == 0
    ang = w0 * np.linspace(0.0, 1.0, exposure_steps) + phase
    px = cx + radius * np.cos(ang)
    py = cy + radius * (np.sin(2 * ang) if vertical else np.sin(ang))
    yy, xx = np.mgrid[0:height, 0:width]
    img = np.zeros((height, width), np.float64)
    for j in range(exposure_steps):
        img += np.exp(-((xx - px[j]) ** 2 + (yy - py[j]) ** 2) / 18.0)
    img /= img.max() + 1e-9
    img = 40.0 + 180.0 * img + rng.normal(0.0, 6.0, size=img.shape)
    pixels = np.clip(np.round(img), 0, 255).astype(np.uint8)
    return Frame(pixels=pixels, duration_us=duration_us, label=label)


@dataclasses.dataclass
class Pool:
    events: List[Events]
    frames: List[Frame]


def make_pool(seed: int, mix: dict, sensors: dict, window_us: int) -> Pool:
    """Every window the run will submit, made once at set-up from the
    seed. ``sensors`` is the configuration adapter's ``sensors(config)``:
    ``{"event": {"height", "width", "num_classes"}}``, with ``"frame"``
    alike when the configuration has a frame wing. Labels cycle through
    the classes, so every seed gets the same mix of gestures."""
    rng = np.random.default_rng(seed)
    dvs = sensors["event"]
    k = dvs["num_classes"]
    events = [gesture_events(
        rng, i % k, duration_us=window_us, mean_events=mix["mean_events"],
        height=dvs["height"], width=dvs["width"], num_classes=k)
        for i in range(mix["pool_windows"])]
    frames = []
    if mix["fusion"]:
        cam = sensors["frame"]
        frames = [gesture_frame(
            rng, i % cam["num_classes"], duration_us=window_us,
            height=cam["height"], width=cam["width"],
            num_classes=cam["num_classes"])
            for i in range(mix["pool_frames"])]
    return Pool(events, frames)


def pad_events(pool_events: List[Events], idx: List[Optional[int]],
               n: int):
    """``(x, y, t, p, valid)``, each ``(len(idx), n)``: the pool's event
    windows ``idx`` padded to ``n`` events; ``None`` rows are empty."""
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


def phases_ms(seed: int, heads: int, period_ms: float) -> np.ndarray:
    """Open-loop phases: evenly spaced over one period, handed to the
    heads in an order drawn from the seed. Every seed offers the same
    arrivals; only which head sends when changes."""
    order = np.random.default_rng(seed + 1).permutation(heads)
    return (order + 0.5) * (period_ms / heads)


def window_index(seed: int, head: int, k: int, n: int) -> int:
    """Which pool entry head ``head`` sends as its ``k``-th window: a
    fixed stride through the pool, offset per head and seed."""
    return (head * 7 + k + seed) % n
