"""A throwaway copy of the benchmark at a toy size, made of data files only.

``make_tiny_root`` writes ``BENCHMARK.json`` with the real cells, metrics
and names, but every configuration and traffic file shrunk, into a temporary
directory.
The rehearsal runs the real runners, generators, references and readers on
it: proof that a configuration, a traffic mix and a cell are data."""

from __future__ import annotations

import json
import os

from benchmarks.harness import REPO

TINY_MODEL = {"vocab_size": 512, "dim": 128, "n_layers": 2, "n_heads": 2,
              "hidden": 256, "max_seq": 512}
TINY_PUBLISHED = {"hidden_size": 128, "num_attention_heads": 2,
                  "num_key_value_heads": 2, "head_dim": 64,
                  "intermediate_size": 256, "vocab_size": 512,
                  "num_hidden_layers": 2, "max_position_embeddings": 512}
TINY_TRAFFIC = {
    "seq2k-b4": {"batch": 2, "seq": 256, "check": {"batch": 1, "seq": 128}},
    "seq8k-b1": {"batch": 1, "seq": 512, "check": {"batch": 1, "seq": 128}},
    "dp4-seq2k-b16": {"batch": 8, "seq": 256,
                      "check": {"batch": 4, "seq": 128}},
    "zipf-b8k": {"corpus_tokens": 40000, "chunk_tokens": 1500,
                 "batch_pairs": 256, "check_tokens": 400},
}
TINY_W2V = {"vocab_size": 4096, "dim": 24}


def _dump(path: str, obj) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def real_bench() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def workloads_by_applies(bench: dict, readers: dict) -> dict:
    """metric -> the cells its reader's ``APPLIES`` holds for among those
    that report the end-to-end metric it moves: what its ``workloads`` in
    ``BENCHMARK.json`` has to be (``harness.reader_applies``)."""
    from benchmarks import harness

    files = {c["name"]: c["file"] for c in bench["configs"]}
    cells = {}
    for w in bench["workloads"]:
        with open(os.path.join(REPO, files[w["config"]])) as f:
            cells[w["name"]] = (json.load(f), w["chips"])
    moved = {m["name"]: set(m.get("workloads", cells))
             for m in bench["end_to_end"]}
    return {name: {cell for cell, (config, chips) in cells.items()
                   if cell in moved[r.MOVES]
                   and harness.reader_applies(r.APPLIES, config, chips)}
            for name, r in readers.items()}


def make_tiny_root(root: str, directory: str = "tinybench") -> dict:
    """Write the toy benchmark under ``root``; returns its BENCHMARK.json."""
    bench = real_bench()
    bench["paths"] = [directory]
    for declared in bench["configs"]:
        with open(os.path.join(REPO, declared["file"])) as f:
            config = json.load(f)
        if config["runner"] == "lm_train":
            config.update(TINY_PUBLISHED)
            config["layer_types"] = config["layer_types"][:2]
            config["model"] = dict(config["model"], **TINY_MODEL)
        else:
            config.update(TINY_W2V)
        declared["file"] = f"{directory}/configs/{declared['name']}.json"
        _dump(os.path.join(root, declared["file"]), config)
    for name, small in TINY_TRAFFIC.items():
        with open(os.path.join(REPO, "benchmarks", "traffic",
                               name + ".json")) as f:
            traffic = json.load(f)
        traffic.update(small)
        traffic["trace_seconds"] = 0.5
        _dump(os.path.join(root, directory, "traffic", name + ".json"),
              traffic)
    _dump(os.path.join(root, "BENCHMARK.json"), bench)
    return bench
