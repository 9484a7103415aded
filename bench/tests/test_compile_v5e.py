"""The served steps compile for a described TPU v5e at the cells' sizes.

Nothing runs: the whole event step (voxelize, the SCNN with its Pallas
kernels, readout) is lowered at 32 slots for one described chip, and at
128 slots as the mesh-sharded step over a described ``v5e:2x2``, with
the kernels in compiled (not interpret) mode. The topology is described
inside a fixture, never at import."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from bench.lib import cells

EVENTS = 65536


@pytest.fixture(scope="module")
def topo():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Kernels compiled through Mosaic, and no persistent cache (a
    described-chip compile cannot be read back without the chip)."""
    import importlib
    from jax.experimental.compilation_cache import compilation_cache as cc
    for name in ("repro.kernels.fc_lif_scan", "repro.kernels.lif_scan"):
        monkeypatch.setattr(importlib.import_module(name), "use_interpret",
                            lambda i=None: False)
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _engine():
    from repro.core import BatchedClosedLoop
    from repro.kernels import lif_scan
    cell = cells.cell("scnn_paper_saturated")
    config = cell.config
    cfg = cell.arch.snn_config(config["snn"])
    from repro.core import init_snn
    params = jax.eval_shape(lambda: init_snn(jax.random.PRNGKey(0), cfg))
    eng = BatchedClosedLoop(params, cfg, lif_scan_fn=lif_scan, fuse_fc=True)
    return eng, cfg, params, config["window_us"]


def _args(params, state, b, params_sh, row_sh, state_sh):
    abs_ = lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s)
    ev = jax.ShapeDtypeStruct((b, EVENTS), jnp.int32, sharding=row_sh)
    valid = jax.ShapeDtypeStruct((b, EVENTS), jnp.bool_, sharding=row_sh)
    return (jax.tree_util.tree_map(lambda a: abs_(a, params_sh), params),
            ev, ev, ev, ev, valid,
            jax.tree_util.tree_map(abs_, state, state_sh))


def test_event_step_compiles_at_32_slots(topo, compiled_kernels):
    from repro.core import snn_init_state
    eng, cfg, params, window_us = _engine()
    one = SingleDeviceSharding(topo.devices[0])
    state = jax.eval_shape(lambda: snn_init_state(cfg, 32))
    args = _args(params, state, 32, one, one,
                 jax.tree_util.tree_map(lambda _: one, state))
    text = jax.jit(eng._build_run(window_us)).lower(*args).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 4


def test_sharded_event_step_compiles_at_128_slots(topo, compiled_kernels):
    from repro.core import snn_init_state
    from repro.core.pipeline import _shard_wrap
    from repro.distributed import make_mesh
    from repro.distributed.sharding import slot_state_pspecs
    eng, cfg, params, window_us = _engine()
    mesh = make_mesh(4, devices=topo.devices)
    state = jax.eval_shape(lambda: snn_init_state(cfg, 128))
    specs = slot_state_pspecs(state, mesh)
    state_sh = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P))
    run = _shard_wrap(eng._build_run(window_us), mesh, state)
    args = _args(params, state, 128, NamedSharding(mesh, P()),
                 NamedSharding(mesh, P("data", None)), state_sh)
    compiled = jax.jit(run).lower(*args).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 4
    for collective in ("all-reduce", "all-gather", "all-to-all",
                       "collective-permute", "reduce-scatter"):
        assert collective not in text
    assert np.isfinite(compiled.memory_analysis().temp_size_in_bytes)
