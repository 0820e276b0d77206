"""Each cell's step compiled ahead of time for the chip, at full size.

libtpu's compiler runs without a chip on a described topology (``v5e:2x2``):
what it refuses here (a kernel Mosaic cannot lower, a step that does not fit
16 GB) would be refused on the chip, and costs no chip time.  Nothing runs,
so nothing here is a measurement.  Skipped where the topology cannot be
described."""

import json
import os

import numpy as np
import pytest

from benchmarks import harness

HBM_LIMIT = 15.75 * 2 ** 30       # what the v5e runtime offers (PR 21)


@pytest.fixture(scope="module")
def topology():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                    # no libtpu, or another chip
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e}")


@pytest.fixture(autouse=True)
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


def param_shapes(model: dict) -> dict:
    """The program's parameter tree for a scanned dense model, as shapes."""
    L, D, H, V = (model[k] for k in ("n_layers", "dim", "hidden",
                                     "vocab_size"))
    return {"embed": (V, D), "out_norm": (D,), "head": (D, V),
            "layers": {"wq": (L, D, D), "wk": (L, D, D), "wv": (L, D, D),
                       "wo": (L, D, D), "attn_norm": (L, D),
                       "mlp_norm": (L, D), "w1": (L, D, H), "w3": (L, D, H),
                       "w2": (L, H, D)}}


def test_param_shapes_are_the_programs():
    """``param_shapes`` stands in for ``init_params`` at full size (which
    draws a billion numbers on the host): held equal at a small size."""
    import jax

    from multiverso_tpu.models import TransformerConfig
    from multiverso_tpu.models.transformer import init_params

    model = dict(vocab_size=96, dim=32, n_layers=3, n_heads=2, hidden=48,
                 scan_layers=True)
    got = jax.tree_util.tree_map(lambda a: a.shape,
                                 init_params(TransformerConfig(**model)))
    assert got == param_shapes(model)


DENSE = ["ouro-2.6b-l16-ut1.seq2k-b4", "ouro-2.6b-l16-ut1.seq8k-b1",
         "ouro-2.6b-l16-ut1.dp4-seq2k-b16"]


@pytest.mark.parametrize("name", DENSE)
def test_dense_step_compiles_for_v5e(topology, monkeypatch, name):
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from multiverso_tpu.models import TransformerConfig, TransformerTrainer
    from multiverso_tpu.models.transformer import param_shardings
    from multiverso_tpu.updaters import AddOption, get_updater

    # The dispatcher asks the process's backend; the target is what counts.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("MVTPU_FORCE_FLASH", raising=False)
    cell = harness.load_cell(name)
    model, traffic = cell.config["model"], cell.traffic
    cfg = TransformerConfig(**model)
    mesh = Mesh(np.asarray(topology.devices[:cell.chips]).reshape(
        traffic["mesh"]["shape"]), tuple(traffic["mesh"]["axes"]))
    # The trainer without its host-side init: only what _raw_step reads.
    trainer = TransformerTrainer.__new__(TransformerTrainer)
    trainer.cfg, trainer.mesh = cfg, mesh
    trainer.updater = get_updater(cell.config["trainer"]["updater_type"])
    trainer.option = AddOption(
        learning_rate=cell.config["trainer"]["learning_rate"])
    params = jax.tree_util.tree_map(
        lambda shape, sharding: jax.ShapeDtypeStruct(
            shape, jnp.float32, sharding=sharding),
        param_shapes(model), param_shardings(cfg, mesh),
        is_leaf=lambda x: isinstance(x, tuple))
    state = jax.tree_util.tree_map(lambda p: (), params)
    tokens = jax.ShapeDtypeStruct(
        (traffic["batch"], traffic["seq"]), jnp.int32,
        sharding=NamedSharding(mesh, P(traffic["mesh"]["axes"][0], None)))
    lowered = jax.jit(trainer._raw_step(), donate_argnums=(0, 1)).lower(
        params, state, tokens)
    # flash_fwd and, since PR 35, the one fused flash_bwd
    assert lowered.as_text().count("tpu_custom_call") == 2
    compiled = lowered.compile()
    peak = harness.compiled_peak_bytes(compiled)
    print(json.dumps({"cell": name, "compiled_peak_gib": peak / 2 ** 30}))
    assert 0.25 * HBM_LIMIT < peak <= HBM_LIMIT
    text = compiled.as_text()
    assert ("all-reduce" in text) == (cell.chips > 1)


def test_sgns_step_compiles_for_v5e(topology, mv_tiny_skipgram):
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    cell = harness.load_cell("w2v-gn3m300.zipf-b8k")
    rows, dim = cell.config["vocab_size"], cell.config["dim"]
    batch, neg = cell.traffic["batch_pairs"], cell.config["negatives"]
    one = SingleDeviceSharding(topology.devices[0])

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    step, _ = mv_tiny_skipgram.make_fused_step()
    table = spec((rows, dim), jnp.float32)
    compiled = step.lower(table, (), table, (), spec((batch,), jnp.int32),
                          spec((batch,), jnp.int32),
                          spec((batch, neg), jnp.int32)).compile()
    peak = harness.compiled_peak_bytes(compiled)
    print(json.dumps({"cell": cell.name, "compiled_peak_gib": peak / 2 ** 30}))
    assert 2 * rows * dim * 4 <= peak <= HBM_LIMIT


@pytest.fixture
def mv_tiny_skipgram():
    """A ``SkipGram`` of few rows at the published dim: its fused step takes
    tables of any number of rows, so the full-size ones are given as
    shapes only."""
    import jax
    from jax.sharding import Mesh

    import multiverso_tpu as mv
    from multiverso_tpu.apps import SkipGram

    mv.init(args=["-updater_type=sgd", "-sync=false", "-log_level=error"],
            mesh=Mesh(np.asarray(jax.devices()[:1]), ("worker",)))
    try:
        yield SkipGram(64, 300, learning_rate=1.0, name="aot_w2v")
    finally:
        mv.shutdown()
