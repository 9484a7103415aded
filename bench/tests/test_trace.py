"""The trace reduction, on a short trace recorded on a TPU v5 lite.

``data/`` holds the profiler trace of 32 stateless event heads and 4
fused event+frame heads served through one ``StreamEngine`` at the
Table II widths, and the HLO text of the two compiled steps. The trace
holds no benchmark spans, so the cut to the measured window is tested
on a window span laid over the middle of its device operations."""
import base64
import gzip
import os
import types

import pytest

from bench.lib import cells, harness, stats, trace, work

DATA = os.path.join(os.path.dirname(__file__), "data")


def _read(name: str) -> bytes:
    with gzip.open(os.path.join(DATA, name + ".gz")) as f:
        return f.read()


@pytest.fixture(scope="module")
def hlo():
    return {w: _read(f"{w}.hlo.txt").decode() for w in ("event", "frame")}


@pytest.fixture(scope="module")
def data():
    import jax
    return jax.profiler.ProfileData.from_serialized_xspace(
        _read("probe.xplane.pb"))


@pytest.fixture(scope="module")
def summary(data, hlo):
    return trace.from_data(data, hlo, 1)


def test_kernels_are_found_by_their_source_file(hlo):
    ev = trace.hlo_index(hlo["event"])[1]
    fr = trace.hlo_index(hlo["frame"])[1]
    assert sorted(ev.values()) == ["fc_lif_scan", "fc_lif_scan",
                                   "lif_scan", "lif_scan"]
    assert list(fr.values()) == ["ternary_matmul"]


def test_instruction_names():
    assert trace.instruction("%fusion.3 = f32[4]{0} fusion(f32[4] %a)") \
        == "fusion.3"
    assert trace.instruction("jit_run(123)") == "jit_run(123)"


def test_summary_is_cut_to_the_measured_window(monkeypatch, data, hlo,
                                               summary):
    whole = summary.chips[0]
    starts = sorted(o.start for o in whole.ops)
    lo, hi = starts[len(starts) // 4], starts[3 * len(starts) // 4]
    monkeypatch.setattr(trace, "host_spans", lambda _: [
        (lo - 10, hi + 10, "step"), (lo, hi, harness.WINDOW_SPAN)])
    cut = trace.from_data(data, hlo, 1, harness.WINDOW_SPAN)
    assert cut.window_s == pytest.approx((hi - lo) / 1e9)
    inside = [o for o in whole.ops if lo <= o.start < hi]
    assert 0 < len(inside) == len(cut.chips[0].ops) < len(whole.ops)
    assert all(lo <= o.start < hi and o.end <= hi for o in cut.chips[0].ops)
    assert all(lo <= a < hi for a, _, _ in cut.chips[0].modules)
    assert 0 < cut.busy_s <= cut.window_s
    assert cut.busy_s < summary.busy_s


def test_modules_and_kernels_are_attributed(summary):
    chip = summary.chips[0]
    n_event = sum(1 for m in chip.modules if m[2] == "event")
    n_frame = sum(1 for m in chip.modules if m[2] == "frame")
    assert n_event >= 1 and n_frame == n_event
    for kernel in ("lif_scan", "fc_lif_scan"):
        calls, seconds = summary.kernel_calls(kernel)
        assert calls == 2 * n_event and seconds > 0
    assert summary.kernel_calls("ternary_matmul")[0] == n_frame
    assert summary.module_ms("event") > summary.module_ms("frame") > 0
    assert 0 < summary.busy_s <= summary.window_s


def test_breakdown_is_short_and_named(summary):
    b = summary.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert all(isinstance(t, float) and t > 0 for _, t in b["device_ops"])
    assert b["device_ops"][0][0].startswith("event:")


def test_roofline_shares_stay_under_100(summary):
    cell = cells.cell("scnn_paper_saturated")
    run = types.SimpleNamespace(trace=summary, config=cell.config,
                                arch=cell.arch, chips=1,
                                peak=work.peaks("TPU v5 lite"))
    for kernel in ("lif_scan", "fc_lif_scan"):
        share = stats.roofline_share(run, kernel)
        assert 0 < share < 100, (kernel, share)


def test_module_name_is_read_from_the_hlo(hlo):
    assert trace.module_name(hlo["event"]) == "jit_run"
    assert trace.module_name(hlo["frame"]) == "jit_run"
    assert trace.module_name("HloModule jit_step.3, is_scheduled=true\n") \
        == "jit_step.3"


def _kernel_line(name: str, kernel: str) -> str:
    body = base64.b64encode(f"loc(/src/repro/kernels/{kernel}.py:7)".encode())
    return (f'  %{name} = f32[4]{{0}} custom-call(f32[4]{{0}} %p), '
            f'custom_call_target="tpu_custom_call", '
            f'backend_config={{"body":"{body.decode()}"}}')


def _hlo(module: str, lines) -> str:
    return "\n".join([f"HloModule {module}, is_scheduled=true", "",
                      "ENTRY %main (p: f32[4]) -> f32[4] {",
                      "  %p = f32[4]{0} parameter(0)", *lines, "}"])


def _event(name: str, start: int, end: int):
    return types.SimpleNamespace(name=name, start_ns=start,
                                 duration_ns=end - start)


def _plane(modules, ops):
    line = lambda n, ev: types.SimpleNamespace(name=n, events=ev)
    return types.SimpleNamespace(name="/device:TPU:0", lines=[
        line(trace.MODULES_LINE, [_event(*m) for m in modules]),
        line(trace.OPS_LINE, [_event(f"%{n} = f32[4]{{0}} op()", a, b)
                              for n, a, b in ops])])


@pytest.mark.parametrize("event_module,frame_module", [
    ("jit_event_step", "jit_frame_step"),    # each wing named its own way
    ("jit_step", "jit_step"),                 # one name, told by the HLO
])
def test_wings_are_tagged_by_their_own_module_name(event_module,
                                                   frame_module):
    hlo = {"event": _hlo(event_module, [
               "  %fusion.1 = f32[4]{0} fusion(f32[4]{0} %p)",
               _kernel_line("lif_scan.2", "lif_scan"),
               "  ROOT %copy.33 = f32[4]{0} copy(f32[4]{0} %p)"]),
           "frame": _hlo(frame_module, [
               "  %convolution.5 = f32[4]{0} convolution(f32[4]{0} %p)",
               "  ROOT %copy.34 = f32[4]{0} copy(f32[4]{0} %p)"])}
    data = types.SimpleNamespace(planes=[_plane(
        modules=[(f"{event_module}(11)", 0, 100),
                 ("jit__move_carries(22)", 200, 300),
                 (f"{frame_module}(33)", 400, 500),
                 ("jit_run(44)", 600, 700)],
        ops=[("fusion.1", 0, 40), ("lif_scan.2", 40, 90),
             ("fusion.1", 200, 250), ("copy.33", 250, 290),
             ("convolution.5", 400, 450), ("copy.34", 450, 480),
             ("lif_scan.2", 600, 650)])])
    chip = trace.read_chips(data, hlo, 1)[0]
    assert [w for _, _, w in chip.modules] == ["event", None, "frame", None]
    by_start = {o.start: o for o in chip.ops}
    assert by_start[0].wing == by_start[40].wing == "event"
    assert by_start[40].kernel == "lif_scan"
    # Another program whose instruction names recur in the event step's
    # HLO, and a module under a name no wing has, stay untagged.
    for t in (200, 250, 600):
        assert by_start[t].wing is None and by_start[t].kernel is None
    assert by_start[200].module == "jit__move_carries"
    assert by_start[600].module == "jit_run"
    assert by_start[400].wing == "frame"
    summary = trace.Summary(window_s=1e-6, chips=[chip], host_spans=[])
    assert summary.module_ms("event") == pytest.approx(90 / 1e6)
    assert summary.kernel_calls("lif_scan") == (1.0, 50 / 1e9)
