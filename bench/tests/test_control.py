"""The control comes out not correct.

The control is the reference itself put in the program's place,
computed in the precision just below the configuration's: its products
see each float32 operand as the two bfloat16 terms of a three-pass
product (``precision="high"``) instead of at HIGHEST. Here it runs at
the published widths on the CPU over a few windows; on the chip it was
run at the cells' own size (``bench/calibrate.py --control``; readings
in PERF.md)."""
import numpy as np
import pytest

from bench.lib import cells, check, harness, traffic


def _sample(n_heads, n_windows, pool):
    return [[harness.Window(head=h, k=k, due=0.0,
                            ev=(7 * h + k) % len(pool.events),
                            fr=(3 * h + k) % max(len(pool.frames), 1)
                            if pool.frames else None)
             for k in range(n_windows)] for h in range(n_heads)]


@pytest.mark.parametrize("name,heads,windows", [
    ("scnn_paper_saturated", 64, 1),
    ("fusion_uav_p80", 4, 4),
])
def test_control_fails_the_configured_limits(name, heads, windows):
    cell = cells.cell(name)
    config, arch = cell.config, cell.arch
    mix = dict(cell.mix, pool_windows=64, pool_frames=4)
    seed = 2 ** 31 + 5
    params = arch.make_weights(seed, config)
    pool = traffic.make_pool(seed, mix, arch.sensors(config),
                             config["window_us"])
    sample = _sample(heads, windows, pool)
    want = arch.reference_rows(params, pool, sample, config)
    again = arch.reference_rows(params, pool, sample, config)
    control = arch.reference_rows(params, pool, sample, config,
                                  precision="high")
    limits = cell.mix["limits"]
    assert check.judge(check.readings(again, want), limits)["ok"]
    verdict = check.judge(check.readings(control, want), limits)
    assert not verdict["ok"], verdict["table"]
    assert np.isfinite([v["value"] for v in verdict["table"].values()]).all()
