"""The start-up and compiler metrics of ISSUE 34: the walk that gives XLA's
own recompute a time, on a hand-made trace; the ``startup.*`` readers against
the program's Dashboard and compile account, and against a program that has
neither; each new reader's constants against its ``BENCHMARK.json`` entry."""

import json
import os

import pytest

from benchmarks import harness, startup
from benchmarks.trace import reduce as R
from benchmarks.trace import xla_remat

CELLS = ["ouro-2.6b-l16-ut1.seq2k-b4", "ouro-2.6b-l16-ut1.seq8k-b1",
         "w2v-gn3m300.zipf-b8k", "ouro-2.6b-l16-ut1.dp4-seq2k-b16",
         "olmoe-1b-7b-e64.zipf-seq4k-b2", "laguna-s-2.1-l5-e16.zipf-seq8k-b1",
         "xing4.0-29b-a4b-e8.zipf-seq8k-b1",
         "ling-3.0-flash-vl-l6.zipf-seq16k-b1"]
XING = [CELLS[-2]]
LM = [c for c in CELLS if not c.startswith("w2v")]
# name -> (unit, source, layer, moves, the cells, APPLIES)
NEW = {
    "startup.import_s": ("s", "program_span", "startup", "setup_s", CELLS,
                         {}),
    "startup.draw_s": ("s", "program_span", "startup", "setup_s", CELLS, {}),
    "startup.place_s": ("s", "program_span", "startup", "setup_s", CELLS,
                        {}),
    "startup.settle_s": ("s", "program_span", "startup", "setup_s", XING,
                         {"runner": "lm_train_latent"}),
    "startup.trace_lower_s": ("s", "program_span", "compiler", "setup_s",
                              CELLS, {}),
    "startup.compile_or_load_s": ("s", "program_span", "compiler", "setup_s",
                                  CELLS, {}),
    "startup.cold_compiles": ("count", "program_counter", "compiler",
                              "setup_s", CELLS, {}),
    "compiler.xla_remat_ms_per_step": ("ms", "device_trace", "compiler",
                                       "tokens_per_chip_s", LM, {}),
}

FUSION = ("%fusion.6830 = bf16[8,8]{1,0:T(8,128)(2,1)} fusion(bf16[8,8]{1,0} "
          "%a), kind=kOutput, calls=%fused_computation.12")
CLONE = FUSION.replace("%fusion.6830 =", "%fusion.6830.remat =")
CLONE2 = FUSION.replace("%fusion.6830 =", "%fusion.6830.remat2.1 =")
# jax.checkpoint's recompute: the name of a called computation, never of the
# instruction
CHECKPOINT = ("%fusion.77 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, "
              "calls=%rematted_computation.remat_body.3")
WHILE = "%while.2 = (s32[]{:T(128)}) while((s32[]{:T(128)}) %t), body=%b"


def trace_with(*ops, steps=2):
    """``ops`` are (instruction text, start, end) on one chip inside a window
    of [0, 100) in which ``steps`` step programs run."""
    dev = R.DeviceLines(
        ops=[R.Event(*op) for op in ops],
        modules=[R.Event(f"jit_step({i})", 50.0 * i, 50.0 * i + 40)
                 for i in range(steps)])
    return R.Trace(devices={"/device:TPU:0": dev},
                   host=[R.Event(R.WINDOW_SPAN, 0.0, 100.0)])


# ------------------------------------------------------ XLA's own recompute
@pytest.mark.parametrize("name, clone", [
    ("fusion.6830.remat", True), ("fusion.6830.remat2", True),
    ("dot.12.remat.1", True), ("fusion.6830.remat2.1", True),
    ("fusion.6830", False), ("rematted_computation.3", False),
    ("fusion.remat_body.4", False), ("checkpoint.rematerialized.2", False)])
def test_a_clone_is_told_by_its_instruction_name(name, clone):
    assert xla_remat.is_clone(f"%{name} = f32[8]{{0}} add(%a, %b)") is clone
    assert xla_remat.is_clone(name) is clone


def test_two_clones_are_timed_and_checkpoint_recompute_is_not():
    found = xla_remat.summarize(trace_with(
        (FUSION, 0, 10), (CLONE, 10, 16), (CHECKPOINT, 16, 30),
        # a while spans the second clone: the clone's time is its own
        (WHILE, 50, 70), (CLONE2, 52, 60)))
    ns = 1e-9
    assert found.step_programs == 2
    assert found.busy_s == pytest.approx(50 * ns)
    assert found.clones_s == pytest.approx(14 * ns)
    assert found.by_instruction_s == [
        ("fusion.6830.remat2.1", pytest.approx(8 * ns)),
        ("fusion.6830.remat", pytest.approx(6 * ns))]


def _reading(trace):
    return harness.Reading(facts={}, trace=trace, peaks={},
                           compiles_in_window=0)


def test_reader_gives_ms_a_step_and_zero_where_none(monkeypatch):
    reader = harness.layer_readers((harness.HERE,))[
        "compiler.xla_remat_ms_per_step"]
    traces = {
        "clones": trace_with((FUSION, 0, 10), (CLONE, 10, 16),
                             (CLONE2, 52, 60)),
        "none": trace_with((FUSION, 0, 10), (CHECKPOINT, 16, 30)),
        "no step program": trace_with((CLONE, 10, 16), steps=0)}
    for key, want in (("clones", 1e3 * 14e-9 / 2), ("none", 0.0),
                      ("no step program", None)):
        monkeypatch.setattr(xla_remat, "of_reading",
                            lambda reading, key=key:
                            xla_remat.summarize(traces[key]))
        got = reader.read(_reading(object()))
        assert got == (pytest.approx(want) if want else want), key
    monkeypatch.undo()
    assert reader.read(_reading(None)) is None           # off the chip
    assert xla_remat.summarize(R.Trace()) is None


# ------------------------------------------------------- the start-up readers
@pytest.fixture
def program():
    """The program's Dashboard and compile account, the first emptied."""
    from multiverso_tpu import compile_cache, dashboard

    dashboard.reset()
    dashboard.reset()
    compile_cache.configure()
    yield dashboard, compile_cache
    dashboard.reset()


def test_monitor_readers_sum_what_ran_and_survive_a_shutdown(program):
    dashboard, _ = program
    readers = harness.layer_readers((harness.HERE,))
    reading = _reading(None)
    for name in ("startup.import_s", "startup.draw_s", "startup.place_s",
                 "startup.settle_s"):
        assert readers[name].read(reading) is None, name    # never ran
    dashboard.get_monitor("mv::import").observe(1.5)
    dashboard.get_monitor("Transformer::init_draw").observe(2.0)
    dashboard.get_monitor("SkipGram::init_draw").observe(0.5)
    dashboard.get_monitor("MatrixTable::init_place").observe(3.0)
    dashboard.get_monitor("MatrixTable::init_place").observe(4.0)
    dashboard.get_monitor("Transformer::balance_router_bias").observe(9.0)
    want = {"startup.import_s": 1.5, "startup.draw_s": 2.5,
            "startup.place_s": 7.0, "startup.settle_s": 9.0}
    for name, value in want.items():
        assert readers[name].read(reading) == pytest.approx(value), name
    dashboard.reset()                  # what ``mv.shutdown()`` does
    for name, value in want.items():
        assert readers[name].read(reading) == pytest.approx(value), name
    # a monitor that was asked for and never observed is not a reading
    assert startup.monitor_s("Transformer::init_place") is None


def test_compile_readers_read_the_account(program):
    import jax
    import jax.numpy as jnp
    import numpy as np

    _, compile_cache = program
    readers = harness.layer_readers((harness.HERE,))
    reading = _reading(None)
    before = compile_cache.account()
    jax.jit(lambda x: jnp.cos(x) * 5)(np.ones(3, np.float32))
    after = compile_cache.account()
    assert after["programs"] == before["programs"] + 1
    assert readers["startup.trace_lower_s"].read(reading) == pytest.approx(
        after["trace_s"] + after["lower_s"])
    assert readers["startup.compile_or_load_s"].read(
        reading) == pytest.approx(after["compile_or_load_s"])
    on_chip = harness.Reading(facts={}, trace=None, compiles_in_window=0,
                              peaks={"bf16_flops_per_s": 197e12})
    assert readers["startup.cold_compiles"].read(on_chip) == after["misses"]
    assert readers["startup.cold_compiles"].read(reading) is None  # rehearsal


def test_a_program_without_them_reads_nothing(monkeypatch):
    """The parent of the PR that added them: no ``account``, no ``ended``,
    no monitor of these names."""
    from multiverso_tpu import compile_cache, dashboard

    monkeypatch.delattr(compile_cache, "account")
    monkeypatch.delattr(dashboard, "ended")
    monkeypatch.setattr(dashboard, "_MONITORS", {})
    readers = harness.layer_readers((harness.HERE,))
    for name in NEW:
        if name.startswith("startup."):
            assert readers[name].read(_reading(None)) is None, name


# ------------------------------------------------------------- the entries
def test_the_entries_match_their_readers():
    with open(os.path.join(harness.REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # in the order they were appended; later PRs' entries follow them
    assert [m["name"] for m in bench["per_layer"]
            if m["name"] in NEW] == list(NEW)
    declared = {m["name"]: m for m in bench["per_layer"]}
    readers = harness.layer_readers((harness.HERE,))
    assert [w["name"] for w in bench["workloads"]][:len(CELLS)] == CELLS
    moved = {m["name"]: m.get("workloads", CELLS)
             for m in bench["end_to_end"]}
    for name, (unit, source, layer, moves, cells, applies) in NEW.items():
        r, m = readers[name], declared[name]
        assert m == {"name": name, "unit": unit, "better": "lower",
                     "source": source, "layer": layer, "moves": moves,
                     "workloads": cells}
        assert (r.NAME, r.UNIT, r.BETTER, r.SOURCE, r.LAYER, r.MOVES) == (
            name, unit, "lower", source, layer, moves)
        assert r.APPLIES == applies
        assert set(cells) <= set(moved[moves])
