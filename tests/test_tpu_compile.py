"""Compile the served Pallas kernels for a described TPU v5e at the
paper's Table II widths (128x128x2 input, pool4, conv16/conv32, fc
2048 -> 512 -> 11, T=16), at B in {1, 8}, and Spikformer-2-256's whole
event step at 32 slots.

Nothing runs: the TPU compiler lowers each kernel for a chip that is
described, not attached, and refuses what the chip would refuse
(unsupported vector ops, unaligned slices, VMEM overcommit) -- the
failures interpret mode cannot show. Each compiled program must contain
the Mosaic kernel (``tpu_custom_call``). The serving layer's state-move
program, plain XLA, is compiled at the same widths.

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


def test_served_event_step_keeps_kernels_findable(one_chip,
                                                  no_persistent_cache,
                                                  monkeypatch):
    """The served event step, as ``BatchedClosedLoop._executable``
    compiles it, for a described v5e at the Table II widths: the step
    keeps its module name and the benchmark's trace reduction
    (``bench.lib.trace.hlo_index``) still finds each Pallas kernel by its
    source file, with the kernels' ``name=`` and the step's named
    scopes."""
    import importlib
    import sys

    from repro.core import BatchedClosedLoop, init_snn
    from repro.kernels import lif_scan
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from bench.lib import trace

    for name in ("repro.kernels.fc_lif_scan", "repro.kernels.lif_scan"):
        monkeypatch.setattr(importlib.import_module(name), "use_interpret",
                            lambda i=None: False)
    eng = BatchedClosedLoop(init_snn(jax.random.PRNGKey(0), CONFIG), CONFIG,
                            lif_scan_fn=lif_scan, fuse_fc=True)
    eng._zero_state_for(1)        # on the host, before the scope below
    (device,) = one_chip.device_set
    with jax.default_device(device):
        text = eng._executable((1, 1024, 300_000)).as_text()
    assert text.startswith(f"HloModule {trace.STEP_MODULE},")
    kernels = trace.hlo_index(text)[1]
    assert sorted(kernels.values()) == ["fc_lif_scan", "fc_lif_scan",
                                        "lif_scan", "lif_scan"]
    assert text.count('custom_call_target="tpu_custom_call"') == 4


def test_spikformer_event_step_compiles_for_v5e(one_chip,
                                               no_persistent_cache,
                                               monkeypatch):
    """Spikformer-2-256's event step (128x128x2 input, SPS 32/64/128/256,
    D=256, 16 heads, L=2, T=16), as ``BatchedClosedLoop._executable``
    compiles it at 32 slots -- 2,048 token rows, so the token linears
    split their rows into blocks: it compiles for a described v5e, keeps
    its module name, every ``lif_scan`` / ``fc_lif_scan`` call stays
    findable by ``bench.lib.trace.hlo_index`` and the named scopes stay
    in the operations' names."""
    import importlib
    import re
    import sys

    from repro.core import BatchedClosedLoop
    from repro.core.spikformer import SpikformerConfig, init_spikformer
    from repro.kernels import lif_scan
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from bench.lib import trace

    for name in ("repro.kernels.fc_lif_scan", "repro.kernels.lif_scan"):
        monkeypatch.setattr(importlib.import_module(name), "use_interpret",
                            lambda i=None: False)
    cfg = SpikformerConfig()
    params = jax.tree_util.tree_map(
        lambda a: jnp.zeros(a.shape, a.dtype),
        jax.eval_shape(lambda: init_spikformer(jax.random.PRNGKey(0), cfg)))
    eng = BatchedClosedLoop(params, cfg, lif_scan_fn=lif_scan, fuse_fc=True)
    eng._zero_state_for(32)       # on the host, before the scope below
    (device,) = one_chip.device_set
    with jax.default_device(device):
        text = eng._executable((32, 1024, 300_000)).as_text()
    assert text.startswith(f"HloModule {trace.STEP_MODULE},")
    kernels = sorted(trace.hlo_index(text)[1].values())
    assert kernels == ["fc_lif_scan"] * 8 + ["lif_scan"] * 7
    ops = set(re.findall(r'op_name="([^"]*)"', text))
    scopes = ["voxelize", "sps0", "sps1", "sps2", "sps3", "rpe", "head",
              "readout"] + [f"block{j}/{s}" for j in range(cfg.depth)
                            for s in ("qkv", "attn", "proj", "mlp")]
    for scope in scopes:
        assert any(n.startswith(f"jit(run)/{scope}/") for n in ops), scope


def test_state_move_compiles_for_v5e(one_chip, no_persistent_cache):
    """The serving layer's state-move program at the Table II widths,
    for a lane of 32 slots and a carry store of 32 home rows: one
    gather re-lays the slots and one scatter parks carries, per state
    leaf."""
    from repro.core.snn import snn_init_state
    from repro.serving.stream import _move_carries

    slots = 32
    planes = [a.shape[1:] for a in
              jax.tree_util.tree_leaves(snn_init_state(CONFIG, 1))]
    k = len(planes)
    text = _compile_text(
        lambda *a: _move_carries(a[:k], a[k:2 * k], a[-1]),
        [((slots, *p), jnp.float32) for p in planes]
        + [((slots + 1, *p), jnp.float32) for p in planes]
        + [((3, slots), jnp.int32)],
        one_chip)
    assert text.count("scatter(") >= k
