"""Operation and byte counts against hand counts at Table II widths, and
the table of peaks."""
import pytest

from bench.lib import cells, work

T, B = 16, 32


CELL = cells.cell("scnn_paper_saturated")


@pytest.fixture(scope="module")
def net():
    return CELL.config["snn"]


def _kernels(b):
    return CELL.arch.kernel_work(CELL.config, b)


def test_fc1_counts_by_hand(net):
    fc1, fc2 = _kernels(B)["fc_lif_scan"]
    assert fc1["ops"] == 2 * T * B * 2048 * 512 + 3 * T * B * 512
    assert fc1["read"] == 4 * (T * B * 2048 + 2048 * 512 + B * 512)
    assert fc1["write"] == 4 * (T * B * 512 + B * 512)
    assert fc2["ops"] == 2 * T * B * 512 * 11 + 3 * T * B * 11


def test_lif_scan_counts_by_hand(net):
    conv1, conv2 = _kernels(B)["lif_scan"]
    n1, n2 = 32 * 32 * 16, 16 * 16 * 32
    assert conv1 == {"ops": 3 * T * B * n1,
                     "read": 4 * (T * B * n1 + B * n1),
                     "write": 4 * (T * B * n1 + B * n1)}
    assert conv2["ops"] == 3 * T * B * n2


def test_window_flops_by_hand(net):
    conv1 = 2 * 32 * 32 * 16 * 9 * 2
    conv2 = 2 * 16 * 16 * 32 * 9 * 16
    fc = 2 * 2048 * 512 + 2 * 512 * 11
    assert CELL.arch.window_flops(CELL.config) == T * (conv1 + conv2 + fc)
    # About 81 MFLOP per window, as the issue counts.
    assert 80e6 < CELL.arch.window_flops(CELL.config) < 82e6


def test_roofline_picks_the_binding_peak(net):
    peak = work.peaks("TPU v5 lite")
    fc1 = _kernels(B)["fc_lif_scan"][0]
    assert work.roofline_s(fc1, peak) == pytest.approx(
        fc1["ops"] / peak["flops_per_s"])
    assert work.bound(fc1, peak) == "compute"
    conv1 = _kernels(B)["lif_scan"][0]
    assert work.bound(conv1, peak) == "memory write"


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        work.peaks("TPU v99")
