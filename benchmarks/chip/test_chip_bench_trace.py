"""The trace-to-metrics reduction, on traces built here."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import serve_step  # noqa: E402
import tracing  # noqa: E402
from tracing import Event, Trace  # noqa: E402

MS = 1e6


def op(name, start_ms, dur_ms, device=0, text=""):
    return Event(name, start_ms * MS, dur_ms * MS, device, "XLA Ops", text)


def host(name, start_ms, dur_ms):
    return Event(name, start_ms * MS, dur_ms * MS, None, "host:python")


# What the chip's compiler makes of the predict step's Pallas call.
BSR = ("%_bsr_topk.1 = f32[8,256]{1,0} custom-call(%a, %b, %pad, %blocks), "
       'custom_call_target="tpu_custom_call" jit(_bsr_topk)/pallas_call')


def small_trace():
    # Window 0-100 ms. Device 0 runs a kernel 10-30 and 25-40 (overlapping
    # events count once), a top-k 60-70; an op outside the window is cut.
    return Trace(events=[
        op("_bsr_topk.1", 10, 20, text=BSR),
        op("_bsr_topk.1", 25, 15, text=BSR),
        op("top-k", 60, 10),
        op("fusion", 95, 10),
        op("fusion", 200, 10),
        host("bench.window", 0, 100),
        host("bench.submit", 42, 16),
    ], window=(0.0, 100 * MS))


def test_busy_and_idle_share():
    tr = small_trace()
    # Union in the window: 10-40 (30 ms) + 60-70 + 95-100 = 45 ms.
    assert tracing.busy_s(tr) == pytest.approx(0.045)
    assert tracing.idle_share(tr) == pytest.approx(0.55)
    assert tr.window_s == pytest.approx(0.1)


def test_busy_is_the_mean_over_devices():
    tr = small_trace()
    tr.events.append(op("fusion", 0, 100, device=1))
    assert tracing.busy_s(tr) == pytest.approx((0.045 + 0.1) / 2)


def test_kernel_time_by_name():
    top = dict(tracing.top_ops(small_trace()))
    assert top["_bsr_topk.1"] == pytest.approx(0.035)
    assert top["top-k"] == pytest.approx(0.010)
    # The op at 95-105 ms counts only its 5 ms inside the window.
    assert top["fusion"] == pytest.approx(0.005)
    hits = tracing.matching(small_trace().ops(), r"f32\[8,256\]")
    assert [e.name for e in hits] == ["_bsr_topk.1", "_bsr_topk.1"]


def test_no_matching_kernel_reads_nothing():
    assert tracing.matching(small_trace().ops(), r"no_such_kernel") == []
    empty = Trace(events=[host("bench.window", 0, 100)], window=(0, 100 * MS))
    assert tracing.busy_s(empty) is None
    assert tracing.idle_share(empty) is None
    g = {"Lp": 256, "Dp": 256, "D": 250, "bl": 128, "bd": 128, "n_blocks": 4}
    ctx = {"trace": Trace(events=[op("fusion", 0, 5)], window=(0, 100 * MS)),
           "geometry": g, "peak": {"bf16_flops_per_s": 1e12,
                                   "hbm_bytes_per_s": 1e11}}
    assert serve_step.roofline_share(ctx, serve_step.KERNEL) is None


def test_idle_gaps_are_named_by_the_open_span():
    gaps = tracing.idle_gaps(small_trace())
    # Gaps: 0-10, 40-60 (bench.submit open at 50), 70-95.
    assert gaps[0] == ["bench.window", pytest.approx(0.025)]
    assert gaps[1] == ["bench.submit", pytest.approx(0.020)]
    assert gaps[2] == ["bench.window", pytest.approx(0.010)]


def test_other_pallas_kernels_are_not_the_bsr_kernel():
    other = ("%_bsr_int8_topk.1 = f32[8,256]{1,0} custom-call(%a), "
             'custom_call_target="tpu_custom_call" '
             "jit(_bsr_int8_topk)/pallas_call")
    tr = Trace(events=[op("_bsr_int8_topk.1", 10, 20, text=other),
                       op("fusion", 30, 5, text="jit(_bsr_topk)/select_n")],
               window=(0.0, 100 * MS))
    assert tracing.matching(tr.ops(), serve_step.PATTERNS[
        serve_step.KERNEL]) == []


def test_a_launch_of_unknown_rows_reads_nothing():
    g = {"Lp": 256, "Dp": 256, "D": 250, "bl": 128, "bd": 128, "n_blocks": 4}
    tr = Trace(events=[op("_bsr_topk.1", 10, 20,
                          text="tpu_custom_call jit(_bsr_topk)/pallas_call")],
               window=(0.0, 100 * MS))
    ctx = {"trace": tr, "geometry": g, "peak": {"bf16_flops_per_s": 1e12,
                                               "hbm_bytes_per_s": 1e11}}
    assert serve_step.roofline_share(ctx, serve_step.KERNEL) is None


def test_roofline_share_of_the_kernel():
    tr = small_trace()
    g = {"Lp": 256, "Dp": 256, "D": 250, "bl": 128, "bd": 128, "n_blocks": 4}
    peak = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
    ctx = {"trace": tr, "geometry": g, "peak": peak}
    share = serve_step.roofline_share(ctx, serve_step.KERNEL)
    # Per launch of 8 rows: 4 blocks of 128 x 128 fp32 (262,144 B) plus
    # x and the scores (8 KB each) over 1e11 B/s beats the FLOPs.
    per = (4 * 128 * 128 * 4 + 4 * 8 * 256 * 2) / 1e11
    assert share == pytest.approx(2 * per / 0.035)


def test_step_share_sizes_each_program_by_the_ops_it_ran():
    g = {"Lp": 256, "Dp": 256, "D": 250, "bl": 128, "bd": 128, "n_blocks": 4}
    peak = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
    step = Event("jit__bsr_topk(12)", 10 * MS, 40 * MS, 0, "XLA Modules")
    tr = Trace(events=[step,
                       op("pad.0", 10, 1, text="%pad.0 = f32[8,256] pad("
                          "f32[8,250] %x.1)"),
                       op("fusion", 45, 3),
                       host("bench.window", 0, 100)], window=(0.0, 100 * MS))
    ctx = {"trace": tr, "geometry": g, "peak": peak}
    per = (4 * 128 * 128 * 4 + 4 * 8 * 256 * 2) / 1e11
    assert serve_step.roofline_share(ctx, serve_step.STEP) == \
        pytest.approx(per / 0.040)
    # A program none of whose ops shows its rows reads nothing.
    tr.events[1] = op("pad.0", 10, 1)
    assert serve_step.roofline_share(ctx, serve_step.STEP) is None


def test_load_reads_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x.T).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    with tracing.capture(str(tmp_path)):
        with tracing.span("bench.window"):
            f(x).block_until_ready()
    tr = tracing.load(str(tmp_path), "bench.window")
    assert tr.window_s > 0
    assert any(e.name == "bench.window" for e in tr.host())
    with pytest.raises(ValueError):
        tracing.load(str(tmp_path), "bench.nothing")
