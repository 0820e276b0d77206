"""ISSUE 40: the EvaByte cell's own files: the walk ``trace/eva.py`` on a
recorded trace of a program that has none of its names (what the parent of the
PR gives: nothing, and no raise), its readers' declarations, and the counts of
``flops_eva.py``."""

import gzip
import json
import os

import pytest

from benchmarks import flops_eva, harness
from benchmarks.trace import eva, program, reduce

FIXTURES = os.path.join(harness.HERE, "trace", "fixtures")
CELL = "evabyte-6.5b-l4.zipf-bytes-seq16k-b1"
NAMES = ("model.attn_eva_ms_per_step", "model.eva_summarise_ms_per_step",
         "kernel.flash_eva_fwd_roofline", "kernel.flash_eva_bwd_roofline")


@pytest.mark.parametrize("fixture", ["toy_1chip", "toy_1chip_scoped"])
def test_a_program_without_the_names_reads_nothing(fixture, tmp_path):
    path = tmp_path / "t.xplane.pb"
    with gzip.open(os.path.join(FIXTURES, fixture + ".xplane.pb.gz")) as f:
        path.write_bytes(f.read())
    with gzip.open(os.path.join(FIXTURES, fixture + ".hlo.txt.gz"), "rt") as f:
        index = program.ScopeIndex([f.read()])
    assert eva.summarize(reduce.load_xplane(str(path)), index) is None
    bare = harness.Reading(facts={}, trace=None, peaks={},
                           compiles_in_window=0)
    readers = harness.layer_readers((harness.HERE,))
    for name in NAMES:
        assert readers[name].read(bare) is None


def test_the_readers_are_declared_as_the_benchmark_lists_them():
    with open(os.path.join(harness.REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    readers = harness.layer_readers((harness.HERE,))
    declared = {m["name"]: m for m in bench["per_layer"]}
    for name in NAMES:
        r, m = readers[name], declared[name]
        assert (r.UNIT, r.BETTER, r.SOURCE, r.LAYER, r.MOVES) == (
            m["unit"], m["better"], m["source"], m["layer"], m["moves"])
        assert m["workloads"] == [CELL]
    cell = harness.load_cell(CELL)
    assert set(NAMES) <= {m["name"] for m in cell.per_layer}
    assert "tokens_per_chip_s" in {m["name"] for m in cell.end_to_end}


def test_the_attention_is_a_twentieth_of_the_step():
    cell = harness.load_cell(CELL)
    model, seq = cell.config["model"], cell.traffic["seq"]
    found = flops_eva.pairs(seq, model["eva_window"], model["eva_chunk"])
    assert found == {"own": 16_785_408, "summary": 7_340_032}
    work = flops_eva.eva_flops(model, 1, seq)
    share = (work["fwd"] + work["bwd"]) / flops_eva.train_flops(model, 1, seq)
    assert 0.05 < share < 0.06
