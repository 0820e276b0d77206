"""``BENCHMARK.json`` against the files it names, and the entry point's
refusals.  What the driver checks before a run is checked here first."""

import json
import os
import re
import subprocess
import sys

import pytest

from benchmarks import harness

REPO = harness.REPO
NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.\-]{0,63}$")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH = re.compile(r"(hidden_size|intermediate_size|latent|state_size|proj|"
                   r"head_dim|_dim$|_rank$|expand|experts_per_tok)")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_keys_names_and_limits(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024
    assert bench["command"] == ["python", "benchmarks/run.py"]
    assert bench["paths"] == ["benchmarks"]
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51
    names = [x["name"] for key in ("configs", "workloads", "end_to_end",
                                   "per_layer") for x in bench[key]]
    assert len(names) == len(set(names)), "a name is used twice"
    assert all(NAME.match(n) for n in names)
    for row in bench["configs"] + bench["workloads"]:
        assert len(row["why"]) <= 200, row["name"]
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert {w["config"] for w in bench["workloads"]} == \
        {c["name"] for c in bench["configs"]}
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert all(w["chips"] in (1, 4) for w in bench["workloads"])
    assert len(four) <= max(1, len(bench["workloads"]) // 4)


def test_what_the_benchmark_holds(bench):
    """The end-to-end metrics, the one four-chip cell, and that no cell or
    metric has left: later PRs append, and only a ``benchmark`` PR takes
    away (its ``PERF.md`` entry says what and why)."""
    cells = [w["name"] for w in bench["workloads"]]
    assert cells[:4] == [
        "ouro-2.6b-l16-ut1.seq2k-b4", "ouro-2.6b-l16-ut1.seq8k-b1",
        "w2v-gn3m300.zipf-b8k", "ouro-2.6b-l16-ut1.dp4-seq2k-b16"]
    assert len(cells) >= 8 and len(bench["configs"]) >= 6
    assert {m["name"] for m in bench["end_to_end"]} >= {
        "setup_s", "tokens_per_chip_s", "pairs_per_chip_s", "peak_hbm_gib"}
    assert [w["name"] for w in bench["workloads"] if w["chips"] == 4] == [
        "ouro-2.6b-l16-ut1.dp4-seq2k-b16"]
    assert not os.path.exists(os.path.join(harness.HERE, "pending"))


def test_metrics_are_declared_soundly(bench):
    check_metrics(bench)


def check_metrics(bench):
    cells = [w["name"] for w in bench["workloads"]]
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
        assert m["better"] in ("higher", "lower")
    for m in bench["per_layer"]:
        assert m["source"] in SOURCES and "bound" not in m
        assert m["moves"] in e2e
        # reported only where the metric it moves is
        moved = e2e[m["moves"]].get("workloads", cells)
        assert set(m.get("workloads", cells)) <= set(moved), m["name"]
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for cell in cells:
        mine = [m for m in bench["end_to_end"]
                if cell in m.get("workloads", cells)]
        assert len(mine) >= 2 and any(m["name"] == "setup_s" for m in mine)
        assert any(cell in m.get("workloads", cells)
                   for m in bench["per_layer"])


def test_every_per_layer_metric_has_its_reader(bench):
    """One file per metric under ``layer_metrics/``, found by listing the
    directory; its declarations are the ones in ``BENCHMARK.json``, and it
    applies by a property of the cell's data, never by a cell's name: among
    the cells that report the metric it moves, those its ``APPLIES`` holds
    for (``harness.reader_applies``) are its ``workloads``."""
    from benchmarks.tests.tiny import workloads_by_applies

    readers = harness.layer_readers((harness.HERE,))
    assert set(readers) == {m["name"] for m in bench["per_layer"]}
    applies = workloads_by_applies(bench, readers)
    cells = [w["name"] for w in bench["workloads"]]
    for m in bench["per_layer"]:
        r = readers[m["name"]]
        assert (r.NAME, r.UNIT, r.BETTER, r.SOURCE, r.LAYER, r.MOVES) == (
            m["name"], m["unit"], m["better"], m["source"], m["layer"],
            m["moves"])
        assert applies[m["name"]] == set(m.get("workloads", cells)), m["name"]


@pytest.mark.parametrize("applies, runner, chips, model, want", [
    ({}, "lm_train", 1, {}, True),
    ({"runner": "lm_train"}, "lm_train_kinds", 1, {}, False),
    ({"runner": ("lm_train", "lm_train_kinds")}, "lm_train_kinds", 1, {},
     True),
    ({"runner": "lm_train", "min_chips": 2}, "lm_train", 1, {}, False),
    ({"runner": "lm_train", "min_chips": 2}, "lm_train", 4, {}, True),
    ({"model": {"num_experts": True}}, "lm_train", 1, {"num_experts": 64},
     True),
    ({"model": {"num_experts": True}}, "lm_train", 1, {"num_experts": 0},
     False),
    ({"model": {"num_experts": False}}, "lm_train", 1, {}, True),
    ({"model": {"kv_lora_rank": True}}, "sgns_train", 1, None, False)])
def test_a_reader_applies_by_the_cells_data(applies, runner, chips, model,
                                            want):
    config = {"runner": runner}
    if model is not None:
        config["model"] = model
    assert harness.reader_applies(applies, config, chips) is want


def test_config_files_hold_the_published_numbers(bench):
    if not os.path.isfile(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        catalog = {row["source_url"]: row for row in map(json.loads, f)}
    files = [c["file"] for c in bench["configs"]]
    assert len(files) == len(set(files))
    for declared in bench["configs"]:
        assert declared["file"].startswith("benchmarks/")
        assert not any(WIDTH.search(k) for k in declared["reduced"])
        with open(os.path.join(REPO, declared["file"])) as f:
            config = json.load(f)
        assert config["source"] == declared["source"]
        assert set(declared["reduced"]) == set(config["reduced"])
        if declared["source"] not in catalog:
            continue
        for key, value in catalog[declared["source"]]["config"].items():
            if key not in declared["reduced"]:
                assert config[key] == value, (declared["name"], key)


def test_cells_find_their_files(bench):
    for w in bench["workloads"]:
        cell = harness.load_cell(w["name"])
        for kind, name in (("runners", cell.runner),
                           ("generators", cell.traffic["generator"]),
                           ("reference", cell.config["reference"])):
            assert harness.load_module(cell.search, kind, name)
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s",
                                                        "peak_hbm_gib"}


def test_off_a_tpu_the_command_refuses_and_prints_no_metric(bench):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, *bench["command"][1:], "--workload",
         bench["workloads"][0]["name"], "--seed", "0", "--seconds", "1",
         "--trace", "0"],
        capture_output=True, text=True, timeout=300, env=env, cwd=REPO)
    assert out.returncode != 0
    assert "'cpu'" in out.stderr and "need 'tpu'" in out.stderr
    assert "metrics" not in out.stdout
    assert "tokens_per_chip_s" not in out.stdout
