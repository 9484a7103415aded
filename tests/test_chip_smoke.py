"""chip_smoke.py's phases at SMOKE / TCN_SMOKE size on the CPU, with the
Pallas kernels in interpret mode: served results agree with the float32
reference, a deliberately broken scan is caught by the same bounds, the
four-device phase holds on forced host devices, and the script itself
refuses to report success on a backend that is not a TPU."""
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402
from repro.configs.colibries import SMOKE, TCN_SMOKE  # noqa: E402
from repro.kernels import lif_scan  # noqa: E402

MEAN_EVENTS = 3000


def test_one_chip_phases_agree_with_reference():
    metrics = chip_smoke.one_chip(0, SMOKE, TCN_SMOKE,
                                  mean_events=MEAN_EVENTS,
                                  require_kernels=False)
    assert metrics["windows"] == (chip_smoke.N_PLAIN + 1) * chip_smoke.N_WINDOWS
    assert metrics["label_agreement"] == 1.0
    for layer in chip_smoke.LAYERS:
        assert metrics[f"spike_agreement_{layer}"] >= \
            chip_smoke.BOUNDS.spike_agreement


def test_broken_scan_fails_the_reference_comparison():
    """A LIF scan that loses each window's last timestep must not pass."""
    def drop_last_step(currents, p, v0):
        return lif_scan(currents.at[-1].set(0.0), p, v0)

    with pytest.raises(chip_smoke.CheckFailed, match="spike agreement"):
        chip_smoke.one_chip(0, SMOKE, TCN_SMOKE, lif_scan_fn=drop_last_step,
                            mean_events=MEAN_EVENTS, require_kernels=False)


def _run(code: str, env_extra: dict) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, env=env,
                          cwd=ROOT, timeout=900)


def test_four_device_phase_on_host_devices():
    out = _run(f"""
        import chip_smoke
        from repro.configs.colibries import SMOKE, TCN_SMOKE
        m = chip_smoke.four_chips(0, SMOKE, TCN_SMOKE,
                                  mean_events={MEAN_EVENTS})
        assert m["sharded_on_all"] and m["collectives"] == 0, m
        print("OK")
    """, {"XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout


@pytest.mark.parametrize("args", [[], ["--four-chips"]])
def test_script_fails_without_a_tpu(args):
    out = subprocess.run([sys.executable, "chip_smoke.py", *args],
                         capture_output=True, text=True, cwd=ROOT,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"),
                         timeout=300)
    assert out.returncode == 1
    assert '"ok"' not in out.stdout
    assert "needs a TPU" in out.stderr
