"""Compile the served Pallas kernels for a described TPU v5e at the
paper's Table II widths (128x128x2 input, pool4, conv16/conv32, fc
2048 -> 512 -> 11, T=16), at B in {1, 8}.

Nothing runs: the TPU compiler lowers each kernel for a chip that is
described, not attached, and refuses what the chip would refuse
(unsupported vector ops, unaligned slices, VMEM overcommit) -- the
failures interpret mode cannot show. Each compiled program must contain
the Mosaic kernel (``tpu_custom_call``).

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU compiler library,
and every test worker imports this file.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.colibries import CONFIG, TCN_CONFIG
from repro.kernels.fc_lif_scan import fc_lif_scan_pallas
from repro.kernels.lif_scan import lif_scan_pallas
from repro.kernels.ternary_matmul import ternary_matmul_pallas

T = CONFIG.time_bins
H0, W0 = CONFIG.post_pool0


@pytest.fixture(scope="module")
def one_chip():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A described-chip compile cannot be read back without the chip, so
    keep these compiles out of the persistent cache."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile_text(fn, shapes, sharding) -> str:
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


LIF_LAYERS = {
    "conv1": (H0, W0, CONFIG.conv1_features),
    "conv2": (H0 // 2, W0 // 2, CONFIG.conv2_features),
}
FC_LAYERS = {
    "fc1": (CONFIG.flat_dim, CONFIG.hidden),
    "fc2": (CONFIG.hidden, CONFIG.num_classes),
}


@pytest.mark.parametrize("b", [1, 8])
@pytest.mark.parametrize("layer", sorted(LIF_LAYERS))
def test_lif_scan_compiles_for_v5e(one_chip, no_persistent_cache, layer, b):
    feat = LIF_LAYERS[layer]
    text = _compile_text(
        lambda c, v: lif_scan_pallas(c, CONFIG.lif, v, interpret=False),
        [((T, b, *feat), jnp.float32), ((b, *feat), jnp.float32)],
        one_chip)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("b", [1, 8])
@pytest.mark.parametrize("layer", sorted(FC_LAYERS))
def test_fc_lif_scan_compiles_for_v5e(one_chip, no_persistent_cache,
                                      layer, b):
    k, n = FC_LAYERS[layer]
    text = _compile_text(
        lambda s, w, v: fc_lif_scan_pallas(s, w, CONFIG.lif, v,
                                           interpret=False),
        [((T, b, k), jnp.float32), ((k, n), jnp.float32),
         ((b, n), jnp.float32)],
        one_chip)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("b", [1, 8])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ternary_matmul_compiles_for_v5e(one_chip, no_persistent_cache,
                                         dtype, b):
    """f32 activations are the CUTIE wing's; bf16 the LM serving path's."""
    k, n = TCN_CONFIG.flat_dim, TCN_CONFIG.hidden
    text = _compile_text(
        lambda x, w, s: ternary_matmul_pallas(x, w, s, interpret=False),
        [((b, k), dtype), ((k // 4, n), jnp.uint8),
         ((n,), jnp.float32)],
        one_chip)
    assert "tpu_custom_call" in text
