"""``benchmarks/trace/program.py`` on hand-written ``op_name``s, hand-made
events and the recorded fixture ``toy_1chip_scoped``; the readers that go through
it, and their entries in ``BENCHMARK.json``."""

import gzip
import json
import os

import pytest

from benchmarks import harness
from benchmarks.trace import program as P
from benchmarks.trace import reduce as R

FIXTURES = os.path.join(harness.HERE, "trace", "fixtures")
STEM = os.path.join(FIXTURES, "toy_1chip_scoped")

NEW = (  # the readers that go through ``program.py`` and read the fixture
    "model.fwd_ms_per_step", "model.bwd_ms_per_step",
    "model.remat_ms_per_step", "updater.ms_per_step",
    "model.head_loss_ms_per_step", "kernel.flash_fwd_roofline",
    "kernel.flash_bwd_roofline", "device.unscoped_share",
    "device.unscoped_share.sgns", "tables.gather_ms_per_step",
    "tables.scatter_apply_ms_per_step", "apps.batcher_span_ms_per_step",
    "host.dispatch_ms_per_step")

# (op_name, phase, scope).  The first rows are the old fixture's (a program
# without scopes), the next the v5e compile's of the dense step at full
# size, the last the fused word2vec step's.
OP_NAMES = [
    ("jit(step)/jvp()/while/body/closed_call/dot_general", "fwd", None),
    ("jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/mul", "remat", None),
    ("jit(step)/transpose(jvp())/while/body/dynamic_update_slice", "bwd",
     None),
    ("jit(step)/jvp()/gather:", "fwd", None),
    ("jit(step)/sub", "other", None),
    ("jit(step)/jvp(embed)/gather", "fwd", "embed"),
    ("jit(step)/jvp(layers)/while/body/closed_call/attn/flash_fwd/"
     "flash_fwd/pallas_call", "fwd", "flash_fwd"),
    ("jit(step)/jvp(layers)/while/body/closed_call/mlp/jit(silu)/logistic",
     "fwd", "mlp"),
    # a split backward kernel is its family's (``KERNELS`` are beginnings)
    ("jit(step)/transpose(jvp(layers))/while/body/closed_call/checkpoint/"
     "attn/flash_bwd_dq/flash_bwd_dq/pallas_call", "bwd", "flash_bwd"),
    ("jit(step)/transpose(jvp(layers))/while/body/closed_call/checkpoint/"
     "attn/flash_bwd/flash_bwd/pallas_call", "bwd", "flash_bwd"),
    ("jit(step)/transpose(jvp(layers))/while/body/attn/attn.sliding/"
     "flash_win_bwd_dkv/flash_win_bwd_dkv/pallas_call", "bwd",
     "flash_win_bwd"),
    ("jit(step)/jvp(layers)/while/body/attn/attn.latent/flash_mla_fwd/"
     "flash_mla_fwd/pallas_call", "fwd", "flash_mla_fwd"),
    ("kda_bwd/transpose(jvp())/reduce_sum", "bwd", "kda_bwd"),
    # the row update's time stays its scope's
    ("jit(step)/tables.scatter_apply/row_update/pallas_call", "other",
     "tables.scatter_apply"),
    ("jit(step)/transpose(jvp(layers))/while/body/closed_call/checkpoint/"
     "rematted_computation/attn/mul", "remat", "attn"),
    ("jit(step)/transpose(jvp(layers))/while/body/dynamic_update_slice",
     "bwd", "layers"),
    ("jit(step)/jvp(loss)/jit(take_along_axis)/gather", "fwd", "loss"),
    ("jit(step)/transpose(jvp(head))/dot_general", "bwd", "head"),
    ("jit(step)/update/sub", "update", "update"),
    ("jit(step)/attn/mul", "other", "attn"),
    ("jit(step)/dynamic_update_slice", "other", None),
    ("jit(step)/updates/mul", "other", None),          # not ``update``
    ("jit(step)/tables.gather/gather", "other", "tables.gather"),
    ("jit(step)/sgns.grad/transpose(jvp(bd,bkd->bk))/dot_general", "bwd",
     "sgns.grad"),
    ("jit(step)/tables.scatter_apply/scatter-add", "other",
     "tables.scatter_apply"),
    ("jit(step)/jvp(jit(take_along_axis))/gather", "fwd", None),
    ("dout", "other", None),
    ("", "other", None),
    (None, "other", None),
]


@pytest.mark.parametrize("op_name,phase,scope", OP_NAMES)
def test_phase_and_scope_match_whole_components(op_name, phase, scope):
    assert P.phase(op_name) == phase
    assert P.scope(op_name) == scope
    assert P.unscoped(op_name) == (phase == "other" and scope is None)


def test_components_strip_the_transformations():
    assert P.components("jit(step)/transpose(jvp(layers))/while") == (
        ("step", ("jit",)), ("layers", ("transpose", "jvp")), ("while", ()))
    assert P.components("a.b/c-d") == (("a.b", ()), ("c-d", ()))


MOSAIC = ('%{} = (bf16[4,8]{{1,0}}, f32[4]{{0}}) custom-call(bf16[4,8]{{1,0}} '
          '%q), custom_call_target="tpu_custom_call"')


def test_a_kernel_is_what_its_calls_names_begin_with():
    body = "jit(step)/jvp(layers)/while/body/attn/"
    assert P.kernel(body + "flash_fwd/flash_fwd/pallas_call") == "flash_fwd"
    for name, family in (("flash_bwd", "flash_bwd"),
                         ("flash_bwd_dq", "flash_bwd"),
                         ("flash_bwd_dkv", "flash_bwd"),
                         ("flash_win_bwd_dq", "flash_win_bwd"),
                         ("flash_mla_bwd_dkv", "flash_mla_bwd"),
                         ("flash_mla_bwd", "flash_mla_bwd"),
                         ("kda_bwd_dq", "kda_bwd"), ("kda_fwd", "kda_fwd")):
        assert P.kernel(f"{body}{name}/{name}/pallas_call") == family, name
    assert P.kernel("jit(step)/tables.scatter_apply/row_update/"
                    "pallas_call") == "row_update"
    assert P.kernel(None) is None
    assert P.kernel("jit(step)/jvp(layers)/attn/pallas_call") is None
    assert P.kernel("jit(step)/jvp(layers)/attn/my_flash_bwd/mul") is None
    # no kernel's name begins another's
    assert not [(a, b) for a in P.KERNELS for b in P.KERNELS
                if a != b and a.startswith(b)]
    # XLA's grouped matmul carries neither scope nor phase: known by name
    assert P.unscoped("ragged-dot-none") and R.is_grouped_matmul(
        MOSAIC.format("ragged-dot-none.8"))
    assert not R.is_grouped_matmul(MOSAIC.format("flash_fwd.6"))
    assert not R.is_grouped_matmul("%ragged-dot-none.8 = f32[8]{0} add(%a)")


HLO = """HloModule jit_step

%fused_computation.1 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %m = f32[8]{0} multiply(%p, %p), metadata={op_name="jit(step)/jvp(mlp)/mul" stack_frame_id=3}
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0), metadata={op_name="params"}
  %copy.1 = f32[8]{0} copy(%a)
  ROOT %fusion.1 = f32[8]{0} fusion(%copy.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/jvp(mlp)/mul" stack_frame_id=3}
}
"""


def test_scope_index_reads_op_names_from_the_text():
    index = P.ScopeIndex([HLO])
    assert index.op_name("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %copy.1), "
                         "kind=kLoop") == "jit(step)/jvp(mlp)/mul"
    assert index.op_name("fusion.1") == "jit(step)/jvp(mlp)/mul"
    assert index.op_name("%copy.1 = f32[8]{0} copy(f32[8]{0} %a)") is None
    assert index.op_name("%a = f32[8]{0} parameter(0)") == "params"


def test_span_totals_count_the_part_inside_the_window():
    host = [R.Event("bench.window", 100, 200),
            R.Event("mv.input.next", 90, 110),        # straddles the start
            R.Event("mv.input.next", 120, 150),
            R.Event("mv.input.next", 190, 230),       # straddles the end
            R.Event("mv.input.next", 10, 20),         # outside
            R.Event("mv.sgns.dispatch", 150, 151),
            R.Event("bench.fetch", 150, 160), R.Event("PjitFunction", 1, 300)]
    totals = P.span_totals(host, 100, 200)
    assert set(totals) == {"mv.input.next", "mv.sgns.dispatch"}
    assert totals["mv.input.next"] == (pytest.approx(50e-9), 3)
    assert totals["mv.sgns.dispatch"] == (pytest.approx(1e-9), 1)


def test_summarize_books_self_time_once():
    """A ``while`` does not count its body; the phases add up to busy time;
    what no name reaches is listed by instruction."""
    ops = {"while.1": "jit(step)/transpose(jvp(layers))/while",
           "fusion.1": "jit(step)/transpose(jvp(layers))/while/body/mlp/mul",
           "fusion.2": "jit(step)/transpose(jvp(layers))/while/body/"
                       "checkpoint/rematted_computation/mlp/mul",
           "fusion.3": "jit(step)/update/sub",
           "flash_fwd.6": "jit(step)/jvp(layers)/attn/flash_fwd/pallas_call",
           "copy.7": "dout"}
    index = P.ScopeIndex()
    index.op_names.update(ops)
    dev = R.DeviceLines(
        ops=[R.Event("%while.1 = () while(() %t), body=%b", 0, 40),
             R.Event("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %a)", 5, 15),
             R.Event("%fusion.2 = f32[8]{0} fusion(f32[8]{0} %a)", 15, 35),
             R.Event(MOSAIC.format("flash_fwd.6"), 50, 60),
             R.Event("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %a)", 60, 64),
             R.Event("%copy.7 = f32[8]{0} copy(f32[8]{0} %x)", 70, 76),
             R.Event("%copy.9 = f32[8]{0} copy(f32[8]{0} %x)", 76, 80),
             R.Event("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %a)", 500, 600)],
        modules=[R.Event("jit_step(123)", 0, 80),
                 R.Event("jit_convert_element_type(9)", 80, 81)])
    trace = R.Trace(devices={"/device:TPU:0": dev},
                    host=[R.Event("bench.window", 0, 100),
                          R.Event("mv.trainer.dispatch", 1, 3)])
    prog = P.summarize(trace, index)
    ns = 1e-9
    assert prog.step_programs == 1
    assert prog.by_phase_s == pytest.approx(
        {"fwd": 10 * ns, "bwd": 20 * ns, "remat": 20 * ns, "update": 4 * ns,
         "other": 10 * ns})
    assert sum(prog.by_phase_s.values()) == pytest.approx(prog.busy_s)
    assert prog.busy_s == pytest.approx(R.summarize(trace).busy_s)
    assert prog.by_scope_s == pytest.approx(
        {"layers": 10 * ns, "mlp": 30 * ns, "flash_fwd": 10 * ns,
         "update": 4 * ns})
    assert prog.by_kernel_s == pytest.approx({"flash_fwd": 10 * ns})
    assert prog.unscoped_s == [("copy.7", pytest.approx(6 * ns)),
                               ("copy.9", pytest.approx(4 * ns))]
    assert prog.spans == {
        "mv.trainer.dispatch": (pytest.approx(2 * ns), 1)}
    assert P.summarize(R.Trace(host=trace.host), index) is None


# ----------------------------------------------------- the recorded fixture
@pytest.fixture(scope="module")
def fixture_repo(tmp_path_factory):
    """The fixture laid out as the harness leaves a traced run: a stand-in
    for the checkout with ``.bench_out/trace/<cell>/plugins/profile/...``."""
    root = tmp_path_factory.mktemp("checkout")
    at = root / ".bench_out" / "trace" / "toy" / "plugins" / "profile" / "1"
    at.mkdir(parents=True)
    with gzip.open(STEM + ".xplane.pb.gz", "rb") as f:
        (at / "toy.xplane.pb").write_bytes(f.read())
    return str(root), str(at / "toy.xplane.pb")


@pytest.fixture(scope="module")
def recorded(fixture_repo):
    path = fixture_repo[1]
    trace = R.load_xplane(path)
    with gzip.open(STEM + ".hlo.txt.gz", "rt") as f:
        texts = [f.read()]
    return trace, P.ScopeIndex.from_xplane(path), P.ScopeIndex(texts), texts


def test_fixture_reduces_to_its_golden_file(recorded):
    from benchmarks.trace.record_scoped_fixture import golden

    with open(STEM + ".golden.json") as f:
        want = json.load(f)
    got = json.loads(json.dumps(golden(STEM)))

    def flat(obj, at=""):
        """Leaves by their path: names compare exactly, numbers closely."""
        if isinstance(obj, dict):
            obj = sorted(obj.items())
        elif isinstance(obj, list):
            obj = enumerate(obj)
        else:
            return {at: obj}
        return {k: v for key, sub in obj
                for k, v in flat(sub, f"{at}/{key}").items()}

    got, want = flat(got), flat(want)
    assert set(got) == set(want)
    assert all(got[k] == (pytest.approx(v) if isinstance(v, float) else v)
               for k, v in want.items())


def test_fixture_phases_add_up_to_the_reductions_busy_time(recorded):
    trace, from_trace, _, texts = recorded
    prog = P.summarize(trace, from_trace)
    summary = R.summarize(trace, R.HloIndex(texts))
    assert prog.step_programs == summary.step_programs > 0
    assert sum(prog.by_phase_s.values()) == pytest.approx(summary.busy_s)
    assert all(prog.by_phase_s[p] > 0 for p in ("fwd", "bwd", "remat",
                                                "update"))
    # the fixture dates from PR 23: its backward is the split pair
    assert set(prog.by_kernel_s) == {"flash_fwd", "flash_bwd"}
    assert sum(prog.by_kernel_s.values()) == pytest.approx(
        summary.by_category_s["mosaic"])
    assert {"mv.input.next", "mv.input.place", "mv.sgns.dispatch",
            "mv.sgns.sync", "mv.sgns.epoch", "mv.trainer.place",
            "mv.trainer.dispatch"} <= set(prog.spans)


def test_the_trace_and_the_text_agree_on_op_names(recorded):
    """Where both name an instruction they say the same, but for names two
    programs of the fixture share (``fusion.1`` of the dense step and of the
    word2vec step); the trace alone knows what the profiler lends."""
    trace, from_trace, from_text, _ = recorded
    events = {e.name for dev in trace.devices.values() for e in dev.ops}
    both = [(from_trace.op_name(e), from_text.op_name(e)) for e in events
            if from_trace.op_name(e) and from_text.op_name(e)]
    same = sum(1 for a, b in both if a == b)
    assert len(both) > 100 and same >= 0.95 * len(both)
    kernels = [e for e in events if R.classify(e) == "mosaic"]
    assert sorted(P.kernel(from_text.op_name(e)) for e in kernels) == \
        sorted(P.kernel(from_trace.op_name(e)) for e in kernels) == \
        ["flash_bwd", "flash_bwd", "flash_fwd"]
    # The compiled program names the custom call after the kernel.
    assert sorted(R.instruction_name(e).split(".")[0] for e in kernels) == \
        ["flash_bwd_dkv", "flash_bwd_dq", "flash_fwd"]


def _reading(trace):
    return harness.Reading(
        facts={"chips": 1, "attention_flops_per_step": 3.0e6,
               "attention_bwd_bytes_per_step": 1.0e3}, trace=trace,
        peaks={"bf16_flops_per_s": 1.97e14, "hbm_bytes_per_s": 8.19e11},
        compiles_in_window=0)


@pytest.mark.parametrize("name", sorted(NEW))
def test_new_reader_reads_the_fixture(fixture_repo, monkeypatch, name):
    monkeypatch.setattr(harness, "REPO", fixture_repo[0])
    P._of_file.cache_clear()
    reader = harness.layer_readers((harness.HERE,))[name]
    value = reader.read(_reading(trace=object()))
    assert value is not None and value > 0
    if reader.UNIT == "%" and not name.endswith("_roofline"):
        assert value <= 100
    # Off the chip the run leaves no device trace and there is no reading.
    assert reader.read(_reading(trace=None)) is None


def test_new_readers_give_nothing_without_a_trace_on_disk(tmp_path,
                                                          monkeypatch):
    monkeypatch.setattr(harness, "REPO", str(tmp_path))
    readers = harness.layer_readers((harness.HERE,))
    assert all(readers[name].read(_reading(trace=object())) is None
               for name in NEW)


def test_a_program_without_scopes_reads_as_absent(tmp_path, monkeypatch):
    """The parent of PR 23, whose trace is the first fixture: phases that
    JAX writes by itself are found, our scopes, kernels and spans are not."""
    at = tmp_path / ".bench_out" / "trace" / "toy" / "plugins" / "profile"
    (at / "1").mkdir(parents=True)
    with gzip.open(os.path.join(FIXTURES, "toy_1chip.xplane.pb.gz")) as f:
        (at / "1" / "toy.xplane.pb").write_bytes(f.read())
    monkeypatch.setattr(harness, "REPO", str(tmp_path))
    P._of_file.cache_clear()
    readers = harness.layer_readers((harness.HERE,))
    got = {name: readers[name].read(_reading(trace=object())) for name in NEW}
    found = {name for name, value in got.items() if value is not None}
    assert found == {"model.fwd_ms_per_step", "model.bwd_ms_per_step",
                     "model.remat_ms_per_step"}


# ------------------------------------------------------------- the entries
def test_new_entries_match_their_readers():
    """``test_contract.py``'s rule for the readers that go through
    ``program.py``: each one's constants are its entry's, and its
    ``workloads`` are the cells its ``APPLIES`` holds for."""
    from benchmarks.tests.tiny import real_bench, workloads_by_applies

    bench = real_bench()
    declared = {m["name"]: m for m in bench["per_layer"]}
    readers = harness.layer_readers((harness.HERE,))
    applies = workloads_by_applies(bench, readers)
    for name in NEW:
        r, m = readers[name], declared[name]
        assert (r.NAME, r.UNIT, r.BETTER, r.SOURCE, r.LAYER, r.MOVES) == (
            m["name"], m["unit"], m["better"], m["source"], m["layer"],
            m["moves"])
        assert set(m["workloads"]) == applies[name], name
