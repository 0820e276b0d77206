"""ISSUE 50: ``benchmarks/trace/parts.py`` on hand-written ``op_name``s and
hand-made events, and the five readers that go through it against their
entries in ``BENCHMARK.json``."""

import json
import os
import types

import pytest

from benchmarks import harness
from benchmarks.trace import parts
from benchmarks.trace import program as P
from benchmarks.trace import reduce as R

READERS = {f"model.{stem}_ms_per_step": part for stem, part in (
    ("attn_proj", "attn.proj"), ("attn_elem", "attn.elem"),
    ("attn_out", "attn.out"), ("mlp_up", "mlp.up"),
    ("mlp_down", "mlp.down"))}
FWD = "jit(step)/jvp(layers)/while/body/closed_call/"
BWD = "jit(step)/transpose(jvp(layers))/while/body/closed_call/checkpoint/"
MS = 1e6                                     # ns

# (op_name, known, under, holds attn, holds mlp)
BOOKED = [
    (FWD + "attn/attn.proj/dot_general", "attn.proj", "attn", True, False),
    (FWD + "attn/attn.linear/attn.proj/dot_general", "attn.proj",
     "attn.linear", True, False),
    # a transformed component is the scope it wraps
    ("jit(step)/transpose(jvp(attn))/transpose(jvp(attn.proj))/dot_general",
     "attn.proj", "attn", True, False),
    (BWD + "rematted_computation/attn/attn.full_nope/attn.elem/mul",
     "attn.elem", "attn.full_nope", True, False),
    (FWD + "attn/attn.eva/attn.out/add", "attn.out", "attn.eva", True,
     False),
    ("jit(step)/jvp(mtp)/attn/attn.latent/attn.elem/concatenate",
     "attn.elem", "attn.latent", True, False),
    (FWD + "mlp/mlp.up/jit(silu)/logistic", "mlp.up", "mlp", False, True),
    (BWD + "mlp/mlp.down/dot_general", "mlp.down", "mlp", False, True),
    # the innermost known component wins: a kernel's name, the summariser
    (FWD + "attn/attn.full/flash_fwd/flash_fwd/pallas_call", "flash_fwd",
     None, True, False),
    (FWD + "attn/attn.elem/flash_bwd_dq/mul", "flash_bwd", None, True,
     False),
    (FWD + "attn/attn.eva/attn.eva.summarise/reduce_sum",
     "attn.eva.summarise", None, True, False),
    (FWD + "attn/attn.eva/flash_eva_fwd/flash_eva_fwd/pallas_call",
     "flash_eva_fwd", None, True, False),
    # what a backward's inner transposes name by the kernel alone
    ("flash_bwd_dq/transpose(jvp())/mul", "flash_bwd", None, False, False),
    ("kda_bwd/transpose(jvp())/reduce_sum", "kda_bwd", None, False, False),
    # nothing known under the scope: what closure counts
    (FWD + "attn/attn.linear/mul", None, None, True, False),
    (FWD + "attn/mul", None, None, True, False),
    (FWD + "mlp/moe.route/top_k", None, None, False, True),
    # whole components only
    (FWD + "attn/attn.projx/mul", None, None, True, False),
    ("jit(step)/update/sub", None, None, False, False),
    ("ragged-dot-none.12", None, None, False, False),
    ("", None, None, False, False),
    (None, None, None, False, False),
]


@pytest.mark.parametrize("op_name,known,under,in_attn,in_mlp", BOOKED)
def test_booked_takes_the_innermost_known_component(op_name, known, under,
                                                    in_attn, in_mlp):
    assert parts.booked(op_name) == (known, under, in_attn, in_mlp)


def test_the_names_are_the_programs():
    """The five scopes as the model opens them, EVA's kernels beside
    ``program.KERNELS``, and no part among ``program.SCOPES`` (the readers
    that were there book by ``attn`` and ``mlp`` as before)."""
    assert parts.PARTS == tuple(READERS.values())
    assert set(parts.KERNELS) == set(P.KERNELS) | {"flash_eva_fwd",
                                                   "flash_eva_bwd"}
    assert not set(parts.PARTS) & set(P.SCOPES)
    for part in parts.PARTS:
        assert P.scope(FWD + f"{part.split('.')[0]}/{part}/mul") == \
            part.split(".")[0]


FUSION = "%fusion.{} = f32[8]{{0}} fusion(f32[8]{{0}} %a), kind=kLoop"
CALL = ('%{} = (bf16[4,8]{{1,0}}, f32[4]{{0}}) custom-call(bf16[4,8]{{1,0}} '
        '%q), custom_call_target="tpu_custom_call"')
HLO = """HloModule jit_step
ENTRY %main {{
{}
}}
"""


def traced(rows, steps=2):
    """One chip, ``steps`` step programs of 100 ms, each running ``rows``
    ``(instruction text, op_name, ms)`` back to back; the ``ScopeIndex`` is
    read from a program text that holds each instruction with its
    ``op_name``."""
    ops, lines = [], []
    for step in range(steps):
        at = step * 100 * MS
        for text, op_name, ms in rows:
            ops.append(R.Event(text, at, at + ms * MS))
            at += ms * MS
    for text, op_name, _ in rows:
        meta = f', metadata={{op_name="{op_name}"}}' if op_name else ""
        lines.append(f"  {text}{meta}")
    device = R.DeviceLines(ops=ops, modules=[
        R.Event("jit_step(1)", s * 100 * MS, (s * 100 + 90) * MS)
        for s in range(steps)])
    trace = R.Trace(devices={"/device:TPU:0": device},
                    host=[R.Event(R.WINDOW_SPAN, 0.0, steps * 100 * MS)])
    return trace, P.ScopeIndex([HLO.format("\n".join(lines))])


ROWS = [
    (FUSION.format(1), FWD + "attn/attn.linear/attn.proj/dot_general", 10),
    (FUSION.format(2), FWD + "attn/attn.linear/attn.elem/mul", 7),
    (FUSION.format(3), FWD + "attn/attn.latent/attn.elem/mul", 3),
    (CALL.format("kda_fwd.4"), FWD + "attn/attn.linear/kda_fwd/kda_fwd/"
     "pallas_call", 20),
    (FUSION.format(5), "kda_bwd/transpose(jvp())/reduce_sum", 5),
    (FUSION.format(6), BWD + "attn/attn.linear/attn.out/dot_general", 4),
    (FUSION.format(7), FWD + "attn/attn.linear/convert_element_type", 1),
    (FUSION.format(8), FWD + "mlp/mlp.up/dot_general", 6),
    (FUSION.format(9), BWD + "mlp/mlp.down/dot_general", 8),
    (FUSION.format(10), FWD + "mlp/moe.route/top_k", 2),
    # XLA's grouped matmul is never a part, whatever name it is lent
    (CALL.format("ragged-dot-none.11"), FWD + "mlp/mlp.up/dot_general", 9),
    (FUSION.format(12), "jit(step)/update/sub", 5),
]


def test_summarize_books_self_time_by_kind_and_part():
    found = parts.summarize(*traced(ROWS))
    assert found.step_programs == 2
    want = {("attn.linear", "attn.proj"): 0.020,
            ("attn.linear", "attn.elem"): 0.014,
            ("attn.latent", "attn.elem"): 0.006,
            ("attn.linear", "attn.out"): 0.008,
            ("mlp", "mlp.up"): 0.012, ("mlp", "mlp.down"): 0.016}
    assert set(found.by_part_s) == set(want)
    for key, seconds in want.items():
        assert found.by_part_s[key] == pytest.approx(seconds), key
    assert found.part_s("attn.elem") == pytest.approx(0.020)
    # ``attn``: parts 24 + the kernel's call 20 + the one open millisecond
    assert found.attn_s == pytest.approx(0.090)
    assert found.attn_open == {
        FWD + "attn/attn.linear/convert_element_type": pytest.approx(0.002)}
    assert found.mlp_s == pytest.approx(0.032)
    assert found.mlp_open == {FWD + "mlp/moe.route/top_k":
                              pytest.approx(0.004)}


def test_a_while_does_not_count_its_body():
    """Self time: a ``while`` that spans a part's fusion adds nothing."""
    loop = "%while.1 = (f32[8]{0}) while((f32[8]{0}) %t), body=%b"
    trace, index = traced([(FUSION.format(1),
                            FWD + "attn/attn.proj/dot_general", 10)],
                          steps=1)
    ops = trace.devices["/device:TPU:0"].ops
    ops.insert(0, R.Event(loop, 0.0, 30 * MS))
    index.op_names["while.1"] = FWD + "attn/attn.proj/while"
    found = parts.summarize(trace, index)
    # 10 ms in the fusion, 20 ms of the loop's own
    assert found.by_part_s[("attn", "attn.proj")] == pytest.approx(0.030)


@pytest.mark.parametrize("rows", [
    [],
    [(FUSION.format(1), FWD + "attn/mul", 10),
     (CALL.format("flash_fwd.2"), FWD + "attn/flash_fwd/flash_fwd/"
      "pallas_call", 5),
     (FUSION.format(3), FWD + "mlp/dot_general", 7)],
    [(FUSION.format(1), "jit(step)/tables.gather/gather", 3)]],
    ids=["empty", "the parent's step", "zipf-b8k"])
def test_a_trace_without_parts_gives_none(rows):
    assert parts.summarize(*traced(rows)) is None
    none = parts.summarize(R.Trace(), P.ScopeIndex())
    assert none is None


@pytest.fixture
def reading(monkeypatch):
    """A reader's ``Reading`` over the hand-made trace."""
    found = parts.summarize(*traced(ROWS))
    monkeypatch.setattr(parts, "of_reading",
                        lambda r: found if r.trace is not None else None)
    return types.SimpleNamespace(facts={}, trace=object(), peaks={},
                                 compiles_in_window=0)


@pytest.mark.parametrize("name,want", [
    ("model.attn_proj_ms_per_step", 10.0),
    ("model.attn_elem_ms_per_step", 10.0),
    ("model.attn_out_ms_per_step", 4.0),
    ("model.mlp_up_ms_per_step", 6.0),
    ("model.mlp_down_ms_per_step", 8.0)])
def test_each_reader_reads_its_part(reading, name, want):
    reader = harness.layer_readers((harness.HERE,))[name]
    assert reader.read(reading) == pytest.approx(want)
    reading.trace = None                     # off the chip
    assert reader.read(reading) is None


def test_a_part_the_step_lacks_reads_zero_and_no_parts_nothing(monkeypatch):
    """All FFNs routed: ``mlp.up`` holds no time and reads 0.0 (the program
    has the scopes); a program with none of the five (the parent) reads
    nothing, so the line leaves the five out."""
    routed = [r for r in ROWS if "mlp." not in (r[1] or "")]
    found = parts.summarize(*traced(routed))
    monkeypatch.setattr(parts, "of_reading", lambda r: found)
    r = types.SimpleNamespace(trace=object())
    assert parts.part_ms_per_step(r, "mlp.up") == 0.0
    assert parts.part_ms_per_step(r, "attn.proj") == pytest.approx(10.0)
    monkeypatch.setattr(parts, "of_reading", lambda r: None)
    for part in parts.PARTS:
        assert parts.part_ms_per_step(r, part) is None


@pytest.mark.parametrize("name", list(READERS))
def test_the_entries_match_their_readers(name):
    """As ``test_contract.py`` holds every entry: the reader's declarations
    are ``BENCHMARK.json``'s, and ``APPLIES = {}`` reaches every cell that
    reports ``tokens_per_chip_s``, in that metric's own order."""
    with open(os.path.join(harness.REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    reader = harness.layer_readers((harness.HERE,))[name]
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert (reader.NAME, reader.UNIT, reader.BETTER, reader.SOURCE,
            reader.LAYER, reader.MOVES, reader.APPLIES) == (
        name, "ms", "lower", "device_trace", "model", "tokens_per_chip_s",
        {})
    assert {k: entry[k] for k in ("unit", "better", "source", "layer",
                                  "moves")} == {
        "unit": reader.UNIT, "better": reader.BETTER,
        "source": reader.SOURCE, "layer": reader.LAYER,
        "moves": reader.MOVES}
    (moved,) = [m for m in bench["end_to_end"]
                if m["name"] == "tokens_per_chip_s"]
    assert entry["workloads"] == moved["workloads"]
    assert len(entry["workloads"]) == 9
    assert READERS[name] in reader.__doc__
    # appended, in the issue's order, after everything that was there
    assert [m["name"] for m in bench["per_layer"][-5:]] == list(READERS)


def test_main_prints_the_split_and_the_remainders(monkeypatch, capsys):
    found = parts.summarize(*traced(ROWS))
    monkeypatch.setattr(parts, "_of_file", lambda path, mtime: found)
    monkeypatch.setattr(os.path, "getmtime", lambda path: 0.0)
    assert parts.main(["parts", "some.xplane.pb"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["steps"] == 2
    assert out["ms_per_step"]["attn.linear/attn.elem"] == 7.0
    assert out["attn"]["open_pct"] == pytest.approx(100 * 1 / 45, abs=1e-3)
    assert out["mlp"]["open_most"] == [[FWD + "mlp/moe.route/top_k", 2.0]]
