"""The cell ``olmoe-1b-7b-e64.zipf-seq4k-b2`` (PR 26), which the older test
files' lists of cells do not name: rehearsed on the CPU at a toy size with
all 64 experts, and its step compiled for the v5e at full size."""

import json
import os
import time

import numpy as np
import pytest

from benchmarks import harness
from benchmarks.tests.tiny import make_tiny_root

CELL = "olmoe-1b-7b-e64.zipf-seq4k-b2"
HBM_LIMIT = 15.75 * 2 ** 30


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """``tiny.py``'s toy benchmark plus this cell's traffic, shrunk."""
    root = str(tmp_path_factory.mktemp("tinyolmoe"))
    make_tiny_root(root)
    with open(os.path.join(harness.HERE, "traffic",
                           "zipf-seq4k-b2.json")) as f:
        traffic = json.load(f)
    traffic.update(batch=2, seq=256, check={"batch": 1, "seq": 128},
                   trace_seconds=0.5)
    with open(os.path.join(root, "tinybench", "traffic",
                           "zipf-seq4k-b2.json"), "w") as f:
        json.dump(traffic, f)
    return root


@pytest.mark.parametrize("trace", [False, True], ids=["trace0", "trace1"])
def test_cell_rehearses(tiny_root, trace, capsys, monkeypatch):
    monkeypatch.setenv("MVTPU_FORCE_FLASH", "interpret")
    cell = harness.load_cell(CELL, root=tiny_root)
    assert (cell.config["model"]["num_experts"],
            cell.config["model"]["top_k"]) == (64, 8)
    result = harness.run_cell(cell, seed=2 ** 31 + 5, seconds=0.5,
                              trace=trace, t_start=time.perf_counter(),
                              rehearsal=True, out_root=tiny_root)
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()
             if x.startswith("{")]
    assert result["correct"] is True, lines
    assert result["attempted"] > 0 and result["failed"] == 0
    # off the chip the MoE readers find no device plane and leave their
    # metrics out; only what the program counts is printed
    assert set(result["metrics"]) == ({"compile.in_window"} if trace
                                      else set())
    check = [x for x in lines if "reference_check" in x][0]["reference_check"]
    assert check["ok"] is True and set(check["grad_rel_err"]) >= {"wq", "w2"}


def test_step_compiles_for_v5e(monkeypatch):
    """Full size, one described chip: the flash kernels in the lowered
    step, XLA's grouped matmul in the compiled one, and a peak that fills
    the chip without passing what the runtime offers."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from multiverso_tpu.models import TransformerConfig, TransformerTrainer
    from multiverso_tpu.models.transformer import param_shardings
    from multiverso_tpu.updaters import AddOption, get_updater

    try:
        topology = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
    except Exception as e:                    # no libtpu, or another chip
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e}")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("MVTPU_FORCE_FLASH", raising=False)
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        cell = harness.load_cell(CELL)
        model, traffic = cell.config["model"], cell.traffic
        cfg = TransformerConfig(**model)
        mesh = Mesh(np.asarray(topology.devices[:1]), ("dp",))
        trainer = TransformerTrainer.__new__(TransformerTrainer)
        trainer.cfg, trainer.mesh = cfg, mesh
        trainer.updater = get_updater(cell.config["trainer"]["updater_type"])
        trainer.option = AddOption(
            learning_rate=cell.config["trainer"]["learning_rate"])
        L, D, H, V, E = (model[k] for k in (
            "n_layers", "dim", "hidden", "vocab_size", "num_experts"))
        shapes = {"embed": (V, D), "out_norm": (D,), "head": (D, V),
                  "layers": {"wq": (L, D, D), "wk": (L, D, D),
                             "wv": (L, D, D), "wo": (L, D, D),
                             "attn_norm": (L, D), "mlp_norm": (L, D),
                             "q_norm": (L, D), "k_norm": (L, D),
                             "router": (L, D, E), "w1": (L, E, D, H),
                             "w3": (L, E, D, H), "w2": (L, E, H, D)}}
        params = jax.tree_util.tree_map(
            lambda shape, sharding: jax.ShapeDtypeStruct(
                shape, jnp.float32, sharding=sharding),
            shapes, param_shardings(cfg, mesh),
            is_leaf=lambda x: isinstance(x, tuple))
        state = jax.tree_util.tree_map(lambda p: (), params)
        tokens = jax.ShapeDtypeStruct(
            (traffic["batch"], traffic["seq"]), jnp.int32,
            sharding=NamedSharding(mesh, P("dp", None)))
        lowered = jax.jit(trainer._raw_step(), donate_argnums=(0, 1)).lower(
            params, state, tokens)
        # remat "full": the forward kernel, its replay in the backward, and
        # (since PR 35) the one fused backward kernel
        assert lowered.as_text().count("tpu_custom_call") == 3
        compiled = lowered.compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    peak = harness.compiled_peak_bytes(compiled)
    print(json.dumps({"cell": CELL, "compiled_peak_gib": peak / 2 ** 30}))
    assert 12 * 2 ** 30 <= peak <= HBM_LIMIT
    assert "ragged-dot" in compiled.as_text()
