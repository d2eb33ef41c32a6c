"""The reduction from a profiler trace to busy time, per-op and per-module
sums and idle gaps keyed by the host span, on a small synthetic trace."""
import pytest

from tracing import Spans, breakdown, reduce_events

MS = 1_000_000  # ns


def synthetic():
    # one device; a 100 ms window; two executions of a decode module
    ops = [("fusion.1", 10 * MS, 10 * MS, None),
           ("copy.2", 15 * MS, 10 * MS, None),       # overlaps fusion.1
           ("custom-call.3", 40 * MS, 5 * MS, None),
           ("fusion.1", 60 * MS, 10 * MS, None),
           ("copy.2", 90 * MS, 20 * MS, None)]        # runs past the window
    modules = [("jit__scheduler_step", 10 * MS, 35 * MS),
               ("jit_prefill", 60 * MS, 10 * MS),
               ("jit__scheduler_step", 90 * MS, 20 * MS)]
    spans = [("cb.window", 0, 100 * MS),
             ("cb.tick", 0, 10 * MS),
             ("cb.engine", 10 * MS, 80 * MS),
             ("cb.admit", 50 * MS, 25 * MS)]
    return {"/device:TPU:0": (ops, modules)}, spans


def test_busy_time_is_the_union_of_op_intervals_in_the_window():
    planes, spans = synthetic()
    red = reduce_events(planes, spans)
    assert red.window_s == pytest.approx(0.1)
    # [10,25] + [40,45] + [60,70] + [90,100] = 15 + 5 + 10 + 10
    assert red.busy_s == pytest.approx(0.040)
    assert red.idle_share == pytest.approx(0.6)


def test_ops_are_summed_by_name_and_by_module():
    planes, spans = synthetic()
    red = reduce_events(planes, spans)
    assert red.op_seconds["copy.2"] == pytest.approx(0.020)
    assert red.op_seconds["fusion.1"] == pytest.approx(0.020)
    assert red.module_seconds["jit__scheduler_step"] == pytest.approx(0.035)
    assert red.module_seconds["jit_prefill"] == pytest.approx(0.010)
    assert red.module_counts["jit__scheduler_step"] == 2


def test_idle_gaps_are_put_down_to_the_host_span():
    planes, spans = synthetic()
    red = reduce_events(planes, spans)
    # gaps: [0,10] tick; [25,40] engine; [45,60] admit covers 10 of 15 ms;
    # [70,90] engine covers 20 (admit 5)
    assert red.idle_by_span["tick"] == pytest.approx(0.010)
    assert red.idle_by_span["admit"] == pytest.approx(0.015)
    assert red.idle_by_span["engine"] == pytest.approx(0.035)
    assert sum(red.idle_by_span.values()) == pytest.approx(0.060)
    assert red.span_counts == {"tick": 1, "engine": 1, "admit": 1}


def test_busy_time_is_averaged_over_devices():
    planes, spans = synthetic()
    planes["/device:TPU:1"] = ([("fusion.9", 0, 100 * MS, "m")], [])
    red = reduce_events(planes, spans)
    assert red.n_devices == 2
    assert red.busy_s == pytest.approx((0.040 + 0.100) / 2)


def test_breakdown_lists_the_largest_entries():
    planes, spans = synthetic()
    bd = breakdown(reduce_events(planes, spans), top=2)
    assert [n for n, _ in bd["device_ops"]] == ["copy.2", "fusion.1"] or \
        [n for n, _ in bd["device_ops"]] == ["fusion.1", "copy.2"]
    assert bd["idle_gaps"][0][0] == "engine"
    assert len(bd["idle_gaps"]) == 2


def test_a_trace_without_a_window_is_an_error():
    planes, spans = synthetic()
    with pytest.raises(ValueError):
        reduce_events(planes, [s for s in spans if s[0] != "cb.window"])


def test_spans_record_durations():
    spans = Spans()
    for _ in range(3):
        with spans("tick"):
            pass
    assert spans.count("tick") == 3 and spans.total("tick") >= 0.0
    assert spans.count("admit") == 0


POOL = "bf16[1024,8,2304]{2,1,0:T(8,128)(2,1)}"
GATHER = ("%banked_gather.156 = bf16[512,8,2304]{2,1,0:T(8,128)(2,1)} "
          "custom-call(s32[512]{0:T(512)} %idx.1, " + POOL + " %pool.3), "
          'custom_call_target="tpu_custom_call"')
SCATTER = ("%custom-call.296 = " + POOL + " custom-call(%copy-done.3, "
           "%copy-done.1, %copy.3), custom_call_target=\"tpu_custom_call\", "
           "operand_layout_constraints={s32[8]{0}, bf16[8,8,2304]{2,1,0}, "
           "bf16[1024,8,2304]{2,1,0}}, output_to_operand_aliasing={{}: "
           "(2, {})}")
COPY = "%copy.912 = " + POOL + " copy(" + POOL + " %custom-call.296)"


def test_hlo_op_text_is_parsed_for_name_opcode_and_label():
    from tracing import DeviceOp, hlo_parts, op_label
    assert hlo_parts(COPY)[:3] == ("copy.912", POOL, "copy")
    assert DeviceOp(COPY, "m", 0, 1).opcode == "copy"
    assert op_label(COPY) == "copy bf16[1024,8,2304]"
    assert op_label("fusion.1") == "fusion.1"
    assert DeviceOp("fusion.1", "m", 0, 1).opcode == ""


def test_kv_kernels_are_told_apart_by_their_shapes():
    from kv_roofline import kv_kernel_call
    row = 8 * 2304 * 2
    assert kv_kernel_call(GATHER) == ("banked_gather",
                                      2 * 512 * row + 512 * 4)
    assert kv_kernel_call(SCATTER) == ("banked_scatter", 2 * 8 * row + 32)
    assert kv_kernel_call(COPY) is None
    assert kv_kernel_call(GATHER.replace("tpu_custom_call", "TopK")) is None


def test_kernel_roofline_is_bytes_at_peak_over_kernel_time():
    from kv_roofline import roofline_pct
    planes = {"/device:TPU:0": ([(GATHER, 0, 100_000, None),
                                 (GATHER, 200_000, 100_000, None),
                                 (COPY, 300_000, 50_000, None)], [])}
    red = reduce_events(planes, [("cb.window", 0, MS)])
    moved = 2 * (2 * 512 * 8 * 2304 * 2 + 2048)
    assert roofline_pct(red, "banked_gather", 819e9) == pytest.approx(
        100 * moved / 819e9 / 200e-6)
    assert roofline_pct(red, "banked_scatter", 819e9) is None
