"""The SmallThinker cell's step compiled ahead of time for the chip, at full
size (``test_aot.py``'s manner, in a file of its own): the finished step at
1 x 16,384 and the published widths, its peak between a quarter and the whole
of what the v5e runtime offers, the eight kernel calls the cell's per-layer
readers read present.  Nothing runs, so nothing here is a measurement.
Skipped where the topology cannot be described."""

import json
import os

import numpy as np
import pytest

from benchmarks import harness

CELL = "smallthinker-21b-a3b-l4-e64.zipf-seq16k-b1-chk8k"
HBM_LIMIT = 15.75 * 2 ** 30       # what the v5e runtime offers (PR 21)
# 16,384 tokens with grouped heads: ``_fused_fits`` turns the fused backward
# away, so every layer's backward is the dq + dkv pair.
KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv", "flash_win_fwd",
           "flash_win_bwd_dq", "flash_win_bwd_dkv")


@pytest.fixture(scope="module")
def one_v5e():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                    # no libtpu, or another chip
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e}")
    return topo.devices[0]


@pytest.fixture
def no_compile_cache():
    """A program compiled for a described chip cannot be read back from the
    persistent cache; keep the cache out of it."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


def test_the_cells_step_compiles_for_v5e(one_v5e, no_compile_cache,
                                         monkeypatch):
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from multiverso_tpu.models import TransformerConfig, TransformerTrainer
    from multiverso_tpu.models.transformer import (init_params,
                                                   param_shardings)
    from multiverso_tpu.updaters import AddOption, get_updater

    # The dispatcher asks the process's backend; the target is what counts.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("MVTPU_FORCE_FLASH", raising=False)
    monkeypatch.delenv("MVTPU_NO_FLASH", raising=False)
    cell = harness.load_cell(CELL)
    model, traffic = cell.config["model"], cell.traffic
    cfg = TransformerConfig(**model)
    mesh = Mesh(np.asarray([one_v5e]), ("dp",))
    # The trainer without its init: only what _raw_step reads.
    trainer = TransformerTrainer.__new__(TransformerTrainer)
    trainer.cfg, trainer.mesh = cfg, mesh
    trainer.updater = get_updater(cell.config["trainer"]["updater_type"])
    trainer.option = AddOption(
        learning_rate=cell.config["trainer"]["learning_rate"])
    shapes = jax.eval_shape(lambda: init_params(cfg, seed=0))
    assert sum(int(np.prod(s.shape)) for s in
               jax.tree_util.tree_leaves(shapes)) == 1_691_752_960
    params = jax.tree_util.tree_map(
        lambda s, sharding: jax.ShapeDtypeStruct(s.shape, jnp.float32,
                                                 sharding=sharding),
        shapes, param_shardings(cfg, mesh))
    state = jax.tree_util.tree_map(lambda p: (), params)
    tokens = jax.ShapeDtypeStruct(
        (traffic["batch"], traffic["seq"]), jnp.int32,
        sharding=NamedSharding(mesh, P("dp", None)))
    lowered = jax.jit(trainer._raw_step(), donate_argnums=(0, 1)).lower(
        params, state, tokens)
    # a layer: the forward, its replay under remat "full", dq, dkv
    assert lowered.as_text().count("tpu_custom_call") == 16
    compiled = lowered.compile()
    peak = harness.compiled_peak_bytes(compiled)
    print(json.dumps({"cell": CELL, "compiled_peak_gib": peak / 2 ** 30}))
    assert 0.25 * HBM_LIMIT < peak <= HBM_LIMIT
    text = compiled.as_text()
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    found = {name: sum(f"/{name}/" in line for line in calls)
             for name in KERNELS}
    print(json.dumps(found))
    # the eight backward calls: a pair the full layer, a pair each window
    # one; a forward a layer and, where the compiler kept it apart, its replay
    backward = {"flash_bwd_dq": 1, "flash_bwd_dkv": 1, "flash_win_bwd_dq": 3,
                "flash_win_bwd_dkv": 3}
    assert {k: found[k] for k in backward} == backward, found
    assert found["flash_fwd"] in (1, 2), found
    assert found["flash_win_fwd"] in (3, 6), found
    assert not any("/flash_bwd/" in line or "/flash_win_bwd/" in line
                   for line in calls)
    # the grouped matmuls are the compiler's own calls
    assert "ragged-dot" in text
    assert "all-reduce" not in text
