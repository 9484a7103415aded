"""The benchmark's reference agrees with the program's own network code
at SMOKE / TCN_SMOKE size on the CPU."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bench.lib import traffic
from bench.reference import cutie, scnn
from bench.tests import smoke

B = 3


ARCH = smoke.cell("fusion_uav_p80").arch


@pytest.fixture(scope="module")
def nets():
    c = smoke.cell("fusion_uav_p80").config
    return c["snn"], c["tcn"], c["window_us"]


@pytest.fixture(scope="module")
def params():
    p = ARCH.make_weights(2 ** 32 + 9, smoke.cell("fusion_uav_p80").config)
    return p["snn"], p["tcn"]


def _events(nets, seed=4):
    snn, _, window_us = nets
    rng = np.random.default_rng(seed)
    wins = [traffic.gesture_events(rng, i, duration_us=window_us,
                                   mean_events=3000, height=snn["height"],
                                   width=snn["width"], num_classes=11)
            for i in range(B)]
    n = max(w.x.shape[0] for w in wins)
    cols = [np.zeros((B, n), np.int32) for _ in range(4)]
    valid = np.zeros((B, n), bool)
    for r, w in enumerate(wins):
        c = w.x.shape[0]
        for col, a in zip(cols, (w.x, w.y, w.t, w.p)):
            col[r, :c] = a
        valid[r, :c] = True
    return (*cols, valid)


def test_voxelize_matches_the_program(nets):
    from repro.core import events as ev
    snn, _, window_us = nets
    x, y, t, p, valid = _events(nets)
    kw = dict(duration_us=window_us, time_bins=snn["time_bins"],
              height=snn["height"], width=snn["width"])
    np.testing.assert_array_equal(
        scnn.voxelize(x, y, t, p, valid, **kw),
        ev.voxelize_batch(x, y, t, p, valid, **kw))


def _program(params, vox, net, state=None):
    from repro.core import snn_apply
    out = snn_apply(params, vox, ARCH.snn_config(net),
                    mode="layer_serial", state=state)
    counts = jnp.stack([out["spikes"][n].sum(axis=tuple(
        a for a in range(out["spikes"][n].ndim) if a != 1))
        for n in scnn.LAYERS], -1)
    return out["out_spikes"].mean(axis=1) * 10.0, counts, out["state"]


def test_scnn_matches_the_program_window_by_window(nets, params):
    snn, _, window_us = nets
    kw = dict(duration_us=window_us, time_bins=snn["time_bins"],
              height=snn["height"], width=snn["width"])
    ref_state, prog_state = None, None
    for seed in (4, 5, 6):   # three chained windows of stateful heads
        vox = scnn.voxelize(*_events(nets, seed), **kw)
        ref = scnn.forward(params[0], vox, snn, ref_state)
        logits, counts, prog_state = _program(params[0], vox, snn,
                                              prog_state)
        ref_state = ref["state"]
        np.testing.assert_array_equal(ref["counts"], counts)
        np.testing.assert_array_equal(ref["logits"], logits)
        assert float(counts.sum()) > 0


def test_cutie_matches_the_program(nets, params):
    from repro.core import pack_tcn, tcn_apply
    from repro.core import frames as fr
    _, tnet, window_us = nets
    rng = np.random.default_rng(1)
    pixels = np.stack([traffic.gesture_frame(
        rng, i, duration_us=window_us, height=tnet["height"],
        width=tnet["width"], num_classes=11).pixels for i in range(B)])
    want = tcn_apply(pack_tcn(params[1]),
                     fr.normalize_frames(pixels[..., None]),
                     ARCH.tcn_config(tnet))["logits"]
    got = cutie.forward(params[1], pixels, tnet)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_pwm_matches_the_program():
    from repro.core.pipeline import pwm_from_logits
    logits = jax.random.normal(jax.random.PRNGKey(0), (5, 11)) * 3
    np.testing.assert_allclose(scnn.pwm(logits), pwm_from_logits(logits),
                               rtol=1e-6, atol=1e-7)


def test_control_rounds_operands_to_sixteen_bits():
    # Two bfloat16 terms keep 1 + 2**-10 of 1 + 2**-10 + 2**-22.
    a = jnp.asarray([1.0 + 2.0 ** -10 + 2.0 ** -22, 3.0], jnp.float32)
    hi_lo = scnn.round_operand(a, "high")
    assert float(hi_lo[0]) == 1.0 + 2.0 ** -10 and float(hi_lo[1]) == 3.0
    assert scnn.round_operand(a, "highest") is a
    with pytest.raises(ValueError):
        scnn.round_operand(a, "low")
