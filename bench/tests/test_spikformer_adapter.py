"""The Spikformer cell at smoke size on the CPU: correct when sound, not
correct with a perturbed logit or a flipped LIF threshold; and the work
counts at published widths pinned to hand counts."""
import copy
import dataclasses
import time
import types

import pytest

from bench.lib import cells, runner, work

CELL = "spikformer_gesture_saturated"
SMOKE = {"height": 32, "width": 32, "sps_channels": [8, 8, 16, 32],
         "embed_dim": 32, "num_heads": 2, "time_bins": 4}


def smoke_cell(build=None) -> cells.Cell:
    """The cell at 32x32, SPS 8/8/16/32, D=32, 2 heads, L=2, T=4, 4 slots,
    small windows and a short pool; ``build`` in place of the adapter's."""
    c = cells.cell(CELL)
    c = dataclasses.replace(c, config=copy.deepcopy(c.config),
                            mix=copy.deepcopy(c.mix))
    c.config["spikformer"].update(SMOKE)
    c.config["slots_per_chip"] = 4
    c.mix.update(mean_events=3000, pool_windows=8, warm_s=0.3, check=8,
                 heads=4)
    if build is not None:
        arch = types.SimpleNamespace(**vars(c.arch))
        arch.build = build
        c = dataclasses.replace(c, arch=arch)
    return c


def _run(c):
    return runner.run_cell(c, 2 ** 31 + 16, 1.0, False, time.perf_counter(),
                           chip=False)


def test_cell_is_correct_when_sound():
    out = _run(smoke_cell())
    assert out["correct"], out["compared"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["compared"]) == {"spike_count_gap", "window_mismatch",
                                    "unserved"}
    assert out["compared"]["spike_count_gap"]["value"] == 0.0


def test_cell_is_not_correct_with_a_perturbed_logit():
    arch = cells.cell(CELL).arch

    def build(config, params, engine_config):
        params = dict(params, head=dict(params["head"],
                                        b=params["head"]["b"] + 0.25))
        return arch.build(config, params, engine_config)

    out = _run(smoke_cell(build))
    assert out["correct"] is False
    assert out["compared"]["window_mismatch"]["value"] == 1.0
    assert out["readings"]["event_logit_gap"] == pytest.approx(0.25,
                                                               abs=1e-5)


def test_cell_is_not_correct_with_a_flipped_lif_threshold():
    """The attention LIF served at the other layers' threshold (1.0 for
    0.5): its spikes, and those of every layer after it, move."""
    arch = cells.cell(CELL).arch

    def build(config, params, engine_config):
        from repro.core import BatchedClosedLoop
        from repro.kernels import lif_scan
        cfg = dataclasses.replace(arch.program_config(config["spikformer"]),
                                  attn_v_th=1.0)
        return [BatchedClosedLoop.from_config(params, cfg, engine_config,
                                              lif_scan_fn=lif_scan)]

    out = _run(smoke_cell(build))
    assert out["correct"] is False
    gap = out["compared"]["spike_count_gap"]
    assert gap["value"] > gap["limit"]


def test_window_flops_and_kernel_work_match_hand_counts():
    c = cells.cell(CELL)
    # Per time step: SPS convs 2*HWC_out*9*C_in = 18.9 M + 3 x 151.0 M,
    # RPE 2*64*256*9*256 = 75.5 M (547.4 M); per block q/k/v, proj and
    # MLP 2*64*256*(768 + 256 + 1024) + 2*64*1024*256 = 100.7 M and
    # attention 2 * 2*64*64*256 = 4.2 M (209.7 M for two); T = 16; then
    # the head once, 2*256*11.
    sps = (2 * 128 * 128 * 32 * 9 * 2 + 2 * 64 * 64 * 64 * 9 * 32
           + 2 * 32 * 32 * 128 * 9 * 64 + 2 * 16 * 16 * 256 * 9 * 128
           + 2 * 64 * 256 * 9 * 256)
    block = (2 * 64 * 256 * (768 + 256 + 1024) + 2 * 64 * 1024 * 256
             + 2 * 2 * 64 * 64 * 256)
    assert sps == 547_356_672 and block == 104_857_600
    assert c.arch.window_flops(c.config) == 16 * (sps + 2 * block) \
        + 2 * 256 * 11 == 12_113_155_584

    kw = c.arch.kernel_work(c.config, 32)
    # lif_scan: SPS0-3 and RPE at 2^23, 2^22, 2^21, 2^20, 2^18 neuron
    # steps per slot, and each block's attention LIF at 2^18.
    assert kw["lif_scan"] == [work.lif_scan(16, 32, n) for n in (
        128 * 128 * 32, 64 * 64 * 64, 32 * 32 * 128, 16 * 16 * 256,
        64 * 256, 64 * 256, 64 * 256)]
    # fc_lif_scan over 32 slots x 64 tokens = 2048 rows: per block q/k/v
    # (256 -> 768), proj (256 -> 256), MLP (256 -> 1024, 1024 -> 256),
    # each with a bias add per current and the bias row read once.
    rows = 32 * 64
    fc = []
    for k, n in [(256, 768), (256, 256), (256, 1024), (1024, 256)] * 2:
        fc.append({"ops": 2 * 16 * rows * k * n + 4 * 16 * rows * n,
                   "read": 4 * (16 * rows * k + k * n + rows * n + n),
                   "write": 4 * (16 * rows * n + rows * n)})
    assert kw["fc_lif_scan"] == fc
    assert kw["fc_lif_scan"][0]["ops"] == 12_985_565_184
