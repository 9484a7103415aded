"""Spikformer served through ``StreamEngine`` -> ``BatchedClosedLoop`` ->
``core/spikformer.py`` at a small size (32x32 input, SPS 8/8/16/32, D=32,
2 heads, L=2, T=4) on seeded weights, against the benchmark's plain
reference (``bench/reference/spikformer.py``), on the CPU with the
Pallas kernels in interpret mode."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import BatchedClosedLoop, EngineConfig, LIFParams
from repro.core import events as ev
from repro.core.spikformer import (SpikformerConfig, init_spikformer,
                                   spikformer_apply)
from repro.kernels import lif_scan
from repro.serving import StreamEngine

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from bench.reference import spikformer as ref  # noqa: E402

CFG = SpikformerConfig(height=32, width=32, sps_channels=(8, 8, 16, 32),
                       num_heads=2, depth=2, time_bins=4, init_gain=3.0)
NET = {"height": 32, "width": 32, "in_channels": 2,
       "sps_channels": [8, 8, 16, 32], "embed_dim": 32, "num_heads": 2,
       "mlp_ratio": 4, "depth": 2, "num_classes": 11, "time_bins": 4,
       "tau": 2.0, "v_th": 1.0, "attn_v_th": 0.5, "attn_scale": 0.125}
WINDOW_US = 300_000
B = 4


@pytest.fixture(scope="module")
def params():
    return init_spikformer(jax.random.PRNGKey(2023), CFG)


@pytest.fixture(scope="module")
def windows():
    rng = np.random.default_rng(16)
    return [ev.synthetic_gesture_events(rng, i % 11, mean_events=3000,
                                        height=32, width=32)
            for i in range(B)]


def _engine(params):
    return BatchedClosedLoop(params, CFG, lif_scan_fn=lif_scan, fuse_fc=True,
                             duration_us=WINDOW_US)


def _vox(windows):
    n = ev.next_pow2(max(w.num_events for w in windows))
    batch = ev.pad_event_windows(windows, max_events=n,
                                 duration_us=WINDOW_US)
    return ev.voxelize_batch(batch.x, batch.y, batch.t, batch.p,
                             batch.valid, duration_us=WINDOW_US,
                             time_bins=CFG.time_bins, height=32, width=32)


def test_served_layers_and_logits_match_the_reference(params, windows):
    """Every LIF layer's spike count and the logits of each window, served
    through ``StreamEngine``, against the time-serial reference with
    spikingjelly's LIF written literally and batch norm unfolded."""
    se = StreamEngine(engines=[_engine(params)],
                      config=EngineConfig(max_streams=B, pipeline_depth=1,
                                          duration_us=WINDOW_US))
    for i, w in enumerate(windows):
        se.open(modality="event", stream_id=i).submit(w)
    got = {r.stream_id: r.result for r in se.run()}
    want = ref.forward(params, _vox(windows), NET)
    names = list(ref.layers(NET))
    sizes = [int(np.prod(s)) for s in ref.layers(NET).values()]
    for i in range(B):
        rates = got[i].breakdown["firing_rates"]
        counts = [round(rates[n] * CFG.time_bins * s)
                  for n, s in zip(names, sizes)]
        # Spikes are thresholds of the same sums: folding batch norm and
        # 1/tau into the weights moves a current by float32 rounding
        # alone, which no neuron here sits close enough to the threshold
        # to feel. So every layer's count is exact.
        np.testing.assert_array_equal(counts, np.asarray(want["counts"][i]))
        # The head's input, a mean of small integers over 2^k values, is
        # exact; the head's 32-term dot product may sum in another order
        # at another batch size: float32 rounding of logits of size ~10.
        np.testing.assert_allclose(got[i].logits[0],
                                   np.asarray(want["logits"][i]),
                                   rtol=0, atol=1e-5)
        assert int(got[i].label_pred[0]) == int(want["label"][i])


def test_spikingjelly_lif_is_lif_scan_with_half_input():
    """spikingjelly's node (tau=2, decay_input, hard reset) is the repo's
    LIF with alpha = 0.5 and input X/2: H = V + (X - V)/2 = V/2 + X/2.
    The two orders of operations round alike whenever every value is
    exact, as here (inputs on a 2^-6 grid, T=8: at most 2^-14 and 3 + 14
    bits); for arbitrary floats they may differ by an ulp, which only a
    membrane within an ulp of the threshold could feel."""
    t, n = 8, 4096
    x = jnp.round(jax.random.normal(jax.random.PRNGKey(5), (t, n)) * 96.0)
    x = jnp.clip(x, -256, 256) / 64.0

    v = jnp.zeros(n)
    sj_spikes = []
    for i in range(t):
        h = v + (x[i] - v) / 2.0
        s = (h >= 1.0).astype(jnp.float32)
        sj_spikes.append(s)
        v = h * (1.0 - s)
    spikes, v_final = lif_scan(x / 2.0, LIFParams(alpha=0.5, v_th=1.0))
    np.testing.assert_array_equal(np.asarray(spikes), np.stack(sj_spikes))
    np.testing.assert_array_equal(np.asarray(v_final), np.asarray(h))
    assert 0.05 < float(spikes.mean()) < 0.5


def test_zero_state_is_a_stateless_call(params, windows):
    eng = _engine(params)
    batch = ev.pad_event_windows(windows, max_events=4096,
                                 duration_us=WINDOW_US)
    stateless = eng.infer_collect(eng.infer_dispatch(batch))
    stateful, state = eng.infer(batch, eng.init_state(B))
    for a, b in zip(stateless, stateful):
        np.testing.assert_array_equal(a.logits, b.logits)
        assert a.breakdown["firing_rates"] == b.breakdown["firing_rates"]
    assert set(state) == {l.name for l in CFG.lif_layers()}
    assert len(state) == 19


def test_two_chained_windows_equal_one_scan(params, windows):
    """Window-chaining contract: T steps then T more from the carried
    membranes equal one uninterrupted 2T-step scan, for every layer's
    spike train and final membrane."""
    vox = _vox(windows)
    vox2 = jnp.concatenate([vox, vox[:, ::-1]], axis=1)   # 2T bins
    run = jax.jit(lambda v, s: spikformer_apply(
        params, v, CFG, lif_scan_fn=lif_scan, fuse_fc=True, state=s))
    whole = run(vox2, None)
    first = run(vox2[:, :CFG.time_bins], None)
    second = run(vox2[:, CFG.time_bins:], first["state"])
    for name in whole["spikes"]:
        np.testing.assert_array_equal(
            np.asarray(whole["spikes"][name]),
            np.concatenate([np.asarray(first["spikes"][name]),
                            np.asarray(second["spikes"][name])]))
        np.testing.assert_array_equal(np.asarray(whole["state"][name]),
                                      np.asarray(second["state"][name]))


def test_no_layer_is_silent_at_the_chosen_gain(params, windows):
    """Every layer fires, none saturates (at this size; the benchmark
    configuration's 2-40% at published widths is read on the chip)."""
    out = jax.jit(lambda v: spikformer_apply(
        params, v, CFG, lif_scan_fn=lif_scan, fuse_fc=True))(_vox(windows))
    rates = {k: float(v.mean())
             for k, v in out["firing_rates_per_stream"].items()}
    assert set(rates) == {l.name for l in CFG.lif_layers()}
    assert all(0.01 <= r <= 0.6 for r in rates.values()), rates


def test_unfused_token_linears_give_the_same_spikes(params, windows):
    """``fuse_fc=False`` (matmul, then the LIF scan) serves the same
    network: every layer's spike count agrees."""
    vox = _vox(windows)
    fused = spikformer_apply(params, vox, CFG, lif_scan_fn=lif_scan,
                             fuse_fc=True)
    plain = spikformer_apply(params, vox, CFG)
    for name, r in fused["firing_rates_per_stream"].items():
        np.testing.assert_array_equal(
            np.asarray(r), np.asarray(plain["firing_rates_per_stream"][name]))


def test_lif_layer_table_names_state_and_volumes():
    full = SpikformerConfig()
    layers = full.lif_layers()
    assert len(layers) == 19
    vols = {l.name: int(np.prod(l.shape)) * full.time_bins for l in layers}
    # Every layer's T x neurons is a power of two, 2^18 to 2^23.
    assert all(v & (v - 1) == 0 and 2 ** 18 <= v <= 2 ** 23
               for v in vols.values())
    assert vols["sps0"] == 2 ** 23 and vols["block1.mlp1"] == 2 ** 20
    assert [l.source for l in layers[:6]] == [None, "sps0", "sps1", "sps2",
                                              "sps3", "rpe"]
