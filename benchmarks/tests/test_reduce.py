"""The trace reduction on hand-made intervals and on the recorded fixture."""

import gzip
import os

import pytest

from benchmarks.trace import reduce as R

FIXTURES = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "trace", "fixtures")

HLO = """HloModule jit_step

%fused_computation.1 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %c = f32[8]{0} convolution(%p, %p), dim_labels=b_f
}

%fused_computation.2 (p: f32[8], i: s32[2]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %i = s32[2]{0} parameter(1)
  ROOT %s = f32[8]{0} scatter(%p, %i, %p), to_apply=%add
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  ROOT %fusion.1 = f32[8]{0} fusion(%a), kind=kOutput, calls=%fused_computation.1
}
"""
MATMUL = ("%fusion.1 = f32[8]{0:T(8)S(1)} fusion(f32[8]{0:T(8)} %a), "
          "kind=kOutput, calls=%fused_computation.1")
SCATTER = ("%fusion.2 = f32[8]{0} fusion(f32[8]{0} %a, s32[2]{0} %i), "
           "kind=kCustom, calls=%fused_computation.2")
WHILE = "%while.2 = (s32[]{:T(128)}) while((s32[]{:T(128)}) %t), body=%b"
COPY = "%copy.3 = f32[8]{0,1:T(8,128)} copy(f32[8]{1,0:T(8,128)S(1)} %x)"
MOSAIC = ('%closed_call.6 = (bf16[4,8]{1,0:T(8,128)(2,1)S(1)}, f32[4]{0}) '
          'custom-call(bf16[4,8]{1,0} %q), custom_call_target='
          '"tpu_custom_call"')
ALLREDUCE = ("%all-reduce-start.1 = (bf16[8]{0}, bf16[8]{0}) "
             "all-reduce-start(bf16[8]{0} %g), channel_id=1")


def test_classify_reads_the_instruction_and_the_fused_computation():
    index = R.HloIndex([HLO])
    assert index.opcodes("fused_computation.1") == {"parameter",
                                                    "convolution"}
    assert R.classify(MATMUL, index) == "matmul"
    assert R.classify(SCATTER, index) == "scatter_gather"
    assert R.classify(WHILE, index) == "control"
    assert R.classify(COPY, index) == "copy"
    assert R.classify(MOSAIC, index) == "mosaic"
    assert R.classify(ALLREDUCE, index) == "collective"
    assert R.classify("%slice-done.2 = f32[8]{0} async-done((f32[8]{0}) "
                      "%slice-start.2)") == "copy"
    # Without the program's text a fusion goes by what XLA named it after.
    assert R.classify("%bitcast_dynamic-update-slice_fusion.3 = f32[8]{0} "
                      "fusion(f32[8]{0} %a), kind=kLoop, calls=%f") == \
        "scatter_gather"
    assert R.classify("%fusion.9 = f32[8]{0} fusion(f32[8]{0} %a), "
                      "kind=kLoop, calls=%f") == "other"
    assert R.instruction_name(MATMUL) == "fusion.1"


def test_interval_arithmetic():
    assert R.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert R.covered_ns([(0, 10), (5, 20), (30, 31)]) == 21
    assert R.subtract([(0, 10), (20, 30)], [(5, 22), (25, 26)]) == \
        [(0, 5), (22, 25), (26, 30)]
    events = [R.Event("outer", 0, 10), R.Event("a", 1, 4),
              R.Event("b", 4, 6), R.Event("leaf", 12, 15)]
    assert [(e.name, s) for e, s in R.self_times(events)] == \
        [("outer", 5), ("a", 3), ("b", 2), ("leaf", 3)]


def hand_made_trace():
    dev = R.DeviceLines(
        ops=[R.Event(MATMUL, 0, 10), R.Event(WHILE, 10, 30),
             R.Event(COPY, 12, 20), R.Event(MOSAIC, 50, 60),
             R.Event(SCATTER, 60, 64)],
        async_ops=[R.Event(ALLREDUCE, 40, 58)],
        modules=[R.Event("jit_step(123)", 0, 30),
                 R.Event("jit_step(123)", 50, 64),
                 R.Event("jit_convert_element_type(9)", 64, 64.5)])
    host = [R.Event(R.WINDOW_SPAN, 0, 100), R.Event("bench.fetch", 28, 52),
            R.Event("PjitFunction(step)", 35, 45),
            R.Event("bench.epoch_chunk", 70, 95)]
    return R.Trace(devices={"/device:TPU:0": dev}, host=host)


def test_summary_of_a_hand_made_trace():
    s = R.summarize(hand_made_trace(), R.HloIndex([HLO]), min_gap_ns=5)
    ns = 1e-9
    assert s.chips == 1 and s.step_programs == 2
    assert s.window_s == pytest.approx(100 * ns)
    # busy: [0,30) + [50,64) = 44; idle 56
    assert s.busy_s == pytest.approx(44 * ns)
    assert s.idle_s == pytest.approx(56 * ns)
    # the while's own time is its 20 less the copy's 8 inside it
    assert s.by_category_s["control"] == pytest.approx(12 * ns)
    assert s.by_category_s["copy"] == pytest.approx(8 * ns)
    assert s.by_category_s["matmul"] == pytest.approx(10 * ns)
    assert s.by_category_s["mosaic"] == pytest.approx(10 * ns)
    assert s.by_category_s["scatter_gather"] == pytest.approx(4 * ns)
    assert sum(s.by_category_s.values()) == pytest.approx(s.busy_s)
    # the all-reduce is in flight over [40,58); compute covers [50,58)
    assert s.collective_s == pytest.approx(18 * ns)
    assert s.collective_exposed_s == pytest.approx(10 * ns)
    # gaps [30,50) and [64,100), named by what the host did in their middle
    assert dict(s.gaps_by_host_s) == pytest.approx({
        "bench.fetch/PjitFunction(step)": 20 * ns,
        "bench.epoch_chunk/python": 36 * ns})
    assert s.longest_gaps[0] == ("bench.epoch_chunk/python",
                                 pytest.approx(36 * ns))
    b = R.breakdown(s)
    assert b["device_ops"][0] == ["while.2 [control]", pytest.approx(12 * ns)]
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert b["idle_gaps"][0][0] == "total: bench.epoch_chunk/python"


def test_no_device_plane_or_no_window_reads_nothing():
    trace = hand_made_trace()
    assert R.summarize(R.Trace(host=trace.host)) is None
    trace.host = [e for e in trace.host if e.name != R.WINDOW_SPAN]
    assert R.summarize(trace) is None


def _fixture(tmp_path, name):
    src = os.path.join(FIXTURES, name + ".gz")
    if not os.path.isfile(src):
        pytest.skip(f"{src} not recorded")
    dst = os.path.join(tmp_path, name)
    with gzip.open(src, "rb") as f, open(dst, "wb") as g:
        g.write(f.read())
    return dst


def _golden(name):
    import json

    with open(os.path.join(FIXTURES, name + ".golden.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", ["toy_1chip", "toy_4chip"])
def test_recorded_fixture_gives_known_numbers(tmp_path, name):
    """A trace recorded on the chip (``record_fixture.py``): the reduction
    gives the numbers written down when it was recorded, and they agree
    with a plain re-count made here."""
    trace = R.load_xplane(_fixture(tmp_path, name + ".xplane.pb"))
    with gzip.open(os.path.join(FIXTURES, name + ".hlo.txt.gz"), "rt") as f:
        index = R.HloIndex([f.read()])
    s = R.summarize(trace, index)
    golden = _golden(name)
    assert s.chips == golden["chips"]
    assert s.step_programs == golden["step_programs"]
    for key in ("window_s", "busy_s", "collective_s", "collective_exposed_s"):
        assert getattr(s, key) == pytest.approx(golden[key], rel=1e-9), key
    for cat, seconds in golden["by_category_s"].items():
        assert s.by_category_s[cat] == pytest.approx(seconds, rel=1e-9,
                                                     abs=1e-15), cat
    assert sum(s.by_category_s.values()) == pytest.approx(s.busy_s, rel=1e-6)
    assert s.by_category_s["mosaic"] > 0          # the flash kernels ran
    assert s.by_category_s["scatter_gather"] > 0  # the table step ran
    if golden["chips"] > 1:
        assert s.collective_s > 0
    # Re-count: an op that holds no other (not a while) has its whole
    # duration as self time; summed by instruction over the window.
    window = [e for e in trace.host if e.name == R.WINDOW_SPAN][0]
    dev = trace.devices[sorted(trace.devices)[0]]
    top_name, _ = next((k, v) for k, v in s.by_op_s if "control" not in k)
    instr = top_name.split(" ")[0]
    recount = sum(min(e.end, window.end) - max(e.start, window.start)
                  for key in trace.devices
                  for e in trace.devices[key].ops
                  if R.instruction_name(e.name) == instr
                  and e.end > window.start and e.start < window.end)
    assert dict(s.by_op_s)[top_name] == pytest.approx(
        recount / len(trace.devices) / 1e9, rel=1e-9)
    assert dev.modules and any(e.name.startswith("bench.")
                               for e in trace.host)
