"""Every cell rehearsed on the CPU at a toy size, through the tests' entry
(``run_cell(..., rehearsal=True)``: no TPU refusal, no device number).

The toy benchmark is made of data files only (``tiny.py``), in a temporary
directory: the runners, generators, references and readers are the real
ones, found by name.  The flash kernels run in interpret mode and the dp4
cell on four virtual devices."""

import json
import os
import time

import pytest

from benchmarks import harness
from benchmarks.tests.tiny import make_tiny_root

CELLS = ["ouro-2.6b-l16-ut1.seq2k-b4", "ouro-2.6b-l16-ut1.seq8k-b1",
         "ouro-2.6b-l16-ut1.dp4-seq2k-b16", "w2v-gn3m300.zipf-b8k"]
KEYS = {"correct", "attempted", "failed", "metrics", "device", "compared"}


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("tinybench"))
    make_tiny_root(root)
    return root


@pytest.fixture(autouse=True)
def interpret_kernels(monkeypatch):
    monkeypatch.setenv("MVTPU_FORCE_FLASH", "interpret")


def rehearse(root, name, trace, capsys):
    cell = harness.load_cell(name, root=root)
    result = harness.run_cell(cell, seed=5, seconds=0.5, trace=trace,
                              t_start=time.perf_counter(), rehearsal=True,
                              out_root=root)
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()
             if x.startswith("{")]
    return cell, result, lines


@pytest.mark.parametrize("trace", [False, True], ids=["trace0", "trace1"])
@pytest.mark.parametrize("name", CELLS)
def test_cell_rehearses(tiny_root, name, trace, capsys):
    cell, result, lines = rehearse(tiny_root, name, trace, capsys)
    assert set(result) == KEYS                     # no breakdown off-chip
    # last on the line: every number compared, beside its limit
    assert list(result)[-1] == "compared" and len(result["compared"]) >= 3
    assert all(set(pair) == {"value", "limit"}
               and pair["value"] <= pair["limit"]
               for pair in result["compared"].values())
    assert result["correct"] is True, lines
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"
    assert set(result["device"]) == {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    # Off the chip nothing that is a time, a rate or a share is printed:
    # only what the program counts.
    declared = {m["name"]: m for m in cell.per_layer + cell.end_to_end}
    for metric, entry in result["metrics"].items():
        assert declared[metric]["source"] == "program_counter", metric
        assert set(entry) == {"value", "unit"}
    if trace:
        counted = [m for m in result["metrics"] if "compile.in_window" in m]
        assert counted and result["metrics"][counted[0]]["value"] == 0.0
    else:
        assert result["metrics"] == {}
    check = [x for x in lines if "reference_check" in x][0]["reference_check"]
    assert check["ok"] is True
    first = [x for x in lines if "losses_first" in x][0]["losses_first"]
    assert len(first) >= 2
    if cell.runner == "lm_train":
        a, b = [x for x in lines if "repeated_batch_losses" in x][0][
            "repeated_batch_losses"]
        assert b < a
    else:
        assert first[-1] < first[0]


def test_the_same_seed_gives_the_same_losses(tiny_root, capsys):
    runs = [rehearse(tiny_root, CELLS[3], False, capsys)[2] for _ in range(2)]
    a, b = ([x for x in r if "losses_first" in x][0]["losses_first"]
            for r in runs)
    n = min(len(a), len(b))
    assert n >= 2 and a[:n] == b[:n]


def test_a_wrong_reference_answer_is_not_correct(tiny_root, capsys,
                                                 monkeypatch):
    cell = harness.load_cell(CELLS[3], root=tiny_root)
    reference = harness.load_module(cell.search, "reference", "sgns")
    monkeypatch.setattr(reference, "ROW_RTOL", 1e-12)
    _, result, lines = rehearse(tiny_root, CELLS[3], False, capsys)
    assert result["correct"] is False
    assert [k for k, pair in result["compared"].items()
            if pair["value"] > pair["limit"]] == ["row_err_in", "row_err_out"]
    assert [x for x in lines if "failed_checks" in x][0]["failed_checks"] == \
        ["reference agrees"]


def test_a_new_cell_metric_and_generator_are_files_and_entries(tmp_path,
                                                               capsys):
    """What PERF.md's "Adding a cell" says, done: a configuration, a
    traffic mix with a generator of its own, and a per-layer metric are
    added to a throwaway benchmark without touching a file that is there."""
    root = str(tmp_path)
    bench = make_tiny_root(root, directory="extra")
    with open(os.path.join(root, "extra/configs/w2v-gn3m300.json")) as f:
        config = dict(json.load(f), name="w2v-new", dim=16)
    with open(os.path.join(root, "extra/configs/w2v-new.json"), "w") as f:
        json.dump(config, f)
    with open(os.path.join(root, "extra/traffic/zipf-b8k.json")) as f:
        traffic = dict(json.load(f), generator="constant_corpus")
    with open(os.path.join(root, "extra/traffic/flat.json"), "w") as f:
        json.dump(traffic, f)
    os.makedirs(os.path.join(root, "extra/generators"))
    with open(os.path.join(root, "extra/generators/constant_corpus.py"),
              "w") as f:
        f.write("import numpy as np\n"
                "from benchmarks.generators.zipf_corpus import chunks\n"
                "def corpus(traffic, vocab_size, seed):\n"
                "    rng = np.random.RandomState(seed)\n"
                "    return rng.randint(64, size=traffic['corpus_tokens'])"
                ".astype(np.int32)\n")
    os.makedirs(os.path.join(root, "extra/layer_metrics"))
    with open(os.path.join(root, "extra/layer_metrics/steps.py"), "w") as f:
        f.write("NAME = 'apps.steps_counted'\nUNIT = 'count'\n"
                "BETTER = 'higher'\nSOURCE = 'program_counter'\n"
                "LAYER = 'apps'\nMOVES = 'pairs_per_chip_s'\n"
                "APPLIES = {'runner': 'sgns_train'}\n"
                "def read(reading):\n    return reading.facts['steps']\n")
    bench["configs"].append({"name": "w2v-new", "source": config["source"],
                             "file": "extra/configs/w2v-new.json",
                             "reduced": ["corpus"], "why": "throwaway"})
    bench["workloads"].append({"name": "w2v-new.flat", "config": "w2v-new",
                               "traffic": "flat", "chips": 1,
                               "why": "throwaway"})
    bench["per_layer"].append({
        "name": "apps.steps_counted", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "apps",
        "moves": "pairs_per_chip_s", "workloads": ["w2v-new.flat"]})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in ("pairs_per_chip_s", "compile.in_window.sgns"):
            m["workloads"].append("w2v-new.flat")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    _, result, _ = rehearse(root, "w2v-new.flat", True, capsys)
    assert result["correct"] is True
    assert result["metrics"]["apps.steps_counted"]["value"] == \
        result["attempted"]
    assert "compile.in_window.sgns" in result["metrics"]
