"""Fused synapse+LIF kernel (fc_lif_scan): oracle equality across shapes,
chunkings and carried state; bitwise parity of the fuse_fc serving path
against the unfused layer_serial path at B in {1, 4, 8}; STBP gradients;
VMEM block selection."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import SNNConfig, init_snn, snn_apply
from repro.core.lif import LIFParams, lif_scan_reference
from repro.kernels import fc_lif_scan, fc_lif_scan_batched
from repro.kernels.fc_lif_scan import (LANES, choose_fc_blocks,
                                       fc_lif_scan_pallas)


def _spikes(key, shape, density=0.25):
    return (jax.random.uniform(jax.random.PRNGKey(key), shape)
            < density).astype(jnp.float32)


def _w(key, k, n, gain=2.0):
    return (jax.random.normal(jax.random.PRNGKey(key), (k, n))
            * gain / np.sqrt(k)).astype(jnp.float32)


SHAPES = [
    (16, 1, 64, 32),      # single stream
    (8, 4, 32, 11),       # fc2-shaped (test config), N < LANES
    (7, 3, 130, 29),      # nothing aligned
    (16, 8, 256, 140),    # batched, N needs lane padding
    (40, 2, 96, 200),     # T chunking path
]


@pytest.mark.parametrize("t,b,k,n", SHAPES)
def test_kernel_matches_matmul_plus_scan_oracle(t, b, k, n):
    s = _spikes(t * 7 + b, (t, b, k))
    w = _w(1, k, n)
    p = LIFParams()
    ref_s, ref_v = lif_scan_reference(jnp.matmul(s, w), p)
    out_s, out_v = fc_lif_scan_pallas(s, w, p, interpret=True)
    np.testing.assert_array_equal(np.asarray(ref_s), np.asarray(out_s))
    np.testing.assert_array_equal(np.asarray(ref_v), np.asarray(out_v))


@pytest.mark.parametrize("block_t", [1, 4, 8, 33])
def test_kernel_chunking_and_carried_state(block_t):
    """Every T-chunking gives the oracle trajectory, with a non-zero v0
    that includes above-threshold components (stateful streaming)."""
    t, b, k, n = 33, 4, 96, 40
    s = _spikes(9, (t, b, k), density=0.3)
    w = _w(2, k, n, gain=1.0)
    v0 = jax.random.uniform(jax.random.PRNGKey(3), (b, n)) * 1.4
    p = LIFParams()
    ref_s, ref_v = lif_scan_reference(jnp.matmul(s, w), p, v0)
    out_s, out_v = fc_lif_scan_pallas(s, w, p, v0, block_t=block_t,
                                      interpret=True)
    np.testing.assert_array_equal(np.asarray(ref_s), np.asarray(out_s))
    np.testing.assert_array_equal(np.asarray(ref_v), np.asarray(out_v))


def test_two_dim_spikes_and_batched_wrapper():
    p = LIFParams()
    s2 = _spikes(5, (12, 64), density=0.3)
    w = _w(6, 64, 20, gain=1.5)
    r_s, r_v = lif_scan_reference(s2 @ w, p)
    o_s, o_v = fc_lif_scan(s2, w, p)
    np.testing.assert_array_equal(np.asarray(r_s), np.asarray(o_s))
    np.testing.assert_array_equal(np.asarray(r_v), np.asarray(o_v))

    sb = _spikes(7, (3, 10, 64), density=0.3)     # (B, T, K) stream-major
    ob, vb = fc_lif_scan_batched(sb, w, p)
    for i in range(3):
        ri_s, ri_v = lif_scan_reference(sb[i] @ w, p)
        np.testing.assert_array_equal(np.asarray(ri_s), np.asarray(ob[i]))
        np.testing.assert_array_equal(np.asarray(ri_v), np.asarray(vb[i]))


def test_window_chaining_via_v_final():
    """Kernel chaining across windows (v0 = previous v_final) equals the
    uninterrupted fused scan, bitwise."""
    t, b, k, n = 24, 2, 64, 48
    s = _spikes(11, (t, b, k), density=0.35)
    w = _w(4, k, n)
    p = LIFParams()
    s_whole, v_whole = fc_lif_scan_pallas(s, w, p, interpret=True)
    s_a, v_a = fc_lif_scan_pallas(s[:10], w, p, interpret=True)
    s_b, v_b = fc_lif_scan_pallas(s[10:], w, p, v_a, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(s_whole),
        np.concatenate([np.asarray(s_a), np.asarray(s_b)]))
    np.testing.assert_array_equal(np.asarray(v_whole), np.asarray(v_b))


def test_gradients_match_stbp_reference():
    t, b, k, n = 10, 2, 48, 24
    s = _spikes(13, (t, b, k), density=0.3)
    w = _w(8, k, n)
    p = LIFParams()

    def loss_k(w_):
        out, v = fc_lif_scan(s, w_, p)
        return (out * jnp.arange(n)).sum() + v.sum()

    def loss_r(w_):
        out, v = lif_scan_reference(jnp.matmul(s, w_), p)
        return (out * jnp.arange(n)).sum() + v.sum()

    g_k = jax.grad(loss_k)(w)
    g_r = jax.grad(loss_r)(w)
    np.testing.assert_allclose(np.asarray(g_k), np.asarray(g_r), rtol=1e-6)
    assert float(jnp.abs(g_k).max()) > 0


def test_choose_fc_blocks_fits_and_raises():
    # Full-model fc1 panel at B=8 fits the default budget with block_t
    # covering the whole Table II scan, all rows in one block.
    bt, br, bn = choose_fc_blocks(16, 8, 2048, 512, jnp.float32)
    assert bt == 16 and br == 8 and bn % LANES == 0
    # Tight budget: block_n shrinks to one lane-row before block_t drops.
    bt2, br2, bn2 = choose_fc_blocks(16, 8, 2048, 512, jnp.float32,
                                     vmem_budget=2 * 1024 * 1024)
    w_bytes = 4 * 2048 * bn2
    state = 2 * 4 * 8 * bn2
    per_t = 8 * (2048 * 4 + bn2 * 8)
    assert br2 == 8
    assert w_bytes + state + bt2 * per_t <= 2 * 1024 * 1024
    with pytest.raises(ValueError, match="vmem_budget"):
        choose_fc_blocks(16, 8, 2048, 512, jnp.float32, vmem_budget=1 << 16)


def _exact_w(key, k, n):
    """Weights on a 2^-6 grid in [-2, 2]: every partial sum of a product
    with small-integer inputs is exact in float32, so no summation order
    can change a current and bitwise equality tests the kernel's
    indexing, bias and LIF update, not the matmul's blocking."""
    return jnp.round(_w(key, k, n, gain=4.0) * 64.0).clip(-128, 128) / 64.0


@pytest.mark.parametrize("t,b,k,n,block_r", [
    (4, 200, 1024, 256, 64),     # rows over four blocks, K=1024, padded
    (3, 48, 256, 140, 16),       # N needs lane padding
])
def test_row_blocks_and_bias_match_unfused(t, b, k, n, block_r):
    """With the row grid axis and a bias, the kernel equals the unfused
    matmul + bias + lif_scan_reference bitwise, from a carried v0 too."""
    x = jnp.floor(jax.random.uniform(jax.random.PRNGKey(b), (t, b, k))
                  * 4.0) * _spikes(k, (t, b, k), density=0.3)
    w = _exact_w(n, k, n)
    bias = jnp.round(jax.random.normal(jax.random.PRNGKey(7), (n,)) * 16) / 64
    v0 = jnp.round(jax.random.uniform(jax.random.PRNGKey(8), (b, n))
                   * 96.0) / 64.0
    p = LIFParams(alpha=0.5, v_th=1.0)
    cur = jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST) + bias
    for v in (None, v0):
        ref_s, ref_v = lif_scan_reference(cur, p, v)
        out_s, out_v = fc_lif_scan_pallas(x, w, p, v, bias=bias,
                                          block_r=block_r, interpret=True)
        np.testing.assert_array_equal(np.asarray(ref_s), np.asarray(out_s))
        np.testing.assert_array_equal(np.asarray(ref_v), np.asarray(out_v))
    assert 0.02 < float(ref_s.mean()) < 0.9


def test_choose_fc_blocks_splits_token_rows():
    """32 slots x 64 tokens at K=1024 (a Spikformer MLP's second layer)
    no longer raises: the rows split into blocks that fit the budget."""
    bt, br, bn = choose_fc_blocks(16, 2048, 1024, 256, jnp.float32)
    assert br < 2048 and br % 8 == 0 and bn % LANES == 0 and bt >= 1
    need = (4 * 1025 * bn + 2 * 4 * br * bn
            + bt * br * (1024 * 4 + bn * 12))
    assert need <= 8 * 1024 * 1024


@pytest.mark.parametrize("t,b,k,n", [(16, 32, 2048, 512),
                                     (16, 32, 512, 11)])
def test_scnn_fc_calls_keep_one_row_block(t, b, k, n):
    """The Table II fc1/fc2 calls at 32 slots keep all rows in one block
    (the kernel's two-axis grid) at the blocks they always had."""
    bt, br, bn = choose_fc_blocks(t, b, k, n, jnp.float32)
    assert br == b
    assert (bt, bn) == ((8, 512) if k == 2048 else (16, 128))


def test_shape_validation():
    p = LIFParams()
    with pytest.raises(ValueError, match="weights K"):
        fc_lif_scan_pallas(_spikes(0, (4, 2, 8)), _w(0, 16, 4), p,
                           interpret=True)
    with pytest.raises(ValueError):
        fc_lif_scan_pallas(_spikes(0, (4, 2, 2, 8)), _w(0, 8, 4), p,
                           interpret=True)
    with pytest.raises(ValueError, match="B, T, K"):
        fc_lif_scan_batched(_spikes(0, (4, 8)), _w(0, 8, 4), p)


# -- the serving hot path: fuse_fc bitwise parity ---------------------------

@pytest.fixture(scope="module")
def cfg():
    return SNNConfig(height=32, width=32, time_bins=8, conv1_features=4,
                     conv2_features=8, hidden=32, num_classes=11)


@pytest.fixture(scope="module")
def params(cfg):
    return init_snn(jax.random.PRNGKey(0), cfg)


@pytest.mark.parametrize("b", [1, 4, 8])
def test_fuse_fc_bitwise_parity(cfg, params, b):
    """snn_apply(fuse_fc=True) must be bitwise identical to the unfused
    layer_serial path -- spikes, membrane placeholder, and every per-
    stream firing rate -- at B in {1, 4, 8}, jit'd and eager."""
    vox = (jax.random.uniform(jax.random.PRNGKey(b),
                              (b, cfg.time_bins, 2, 32, 32))
           < 0.1).astype(jnp.float32)
    base = snn_apply(params, vox, cfg, mode="layer_serial")
    fused = snn_apply(params, vox, cfg, mode="layer_serial", fuse_fc=True)
    jit_fused = jax.jit(
        lambda p, v: snn_apply(p, v, cfg, mode="layer_serial",
                               fuse_fc=True))(params, vox)
    for got in (fused, jit_fused):
        np.testing.assert_array_equal(np.asarray(base["out_spikes"]),
                                      np.asarray(got["out_spikes"]))
        for k in base["firing_rates_per_stream"]:
            np.testing.assert_array_equal(
                np.asarray(base["firing_rates_per_stream"][k]),
                np.asarray(got["firing_rates_per_stream"][k]))


def test_fuse_fc_requires_layer_serial(cfg, params):
    vox = jnp.zeros((1, cfg.time_bins, 2, 32, 32))
    with pytest.raises(ValueError, match="layer_serial"):
        snn_apply(params, vox, cfg, mode="time_serial", fuse_fc=True)
