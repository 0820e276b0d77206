"""A 2-D table's resting layout (docs/embedding.md "Resting layout").

A ``MatrixTable`` of a lane (128 columns) or wider stores its rows padded to
the lane tile, so that the backend's default layout for the buffer is
row-major.  The CPU holds the contract: the eager API still speaks
``num_cols`` and gives the values a plain numpy table gives, the padding
stays zero through every program, a fused step does not compile again.  What the
padding buys exists only on the TPU, whose compiler runs here on a described
``v5e:2x2`` (nothing executes, so nothing below is a measurement), at the
published word2vec size.
"""

import os
import re

import numpy as np
import pytest

ROWS, DIM, STORED, BATCH, NEG = 3_000_000, 300, 384, 8192, 5


# ------------------------------------------------------------ v5e compile

@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                    # no libtpu, or another chip
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e}")
    return SingleDeviceSharding(topo.devices[0])


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


def compile_sgns_step(mv, one_chip, cols):
    """The fused step of a small ``SkipGram`` (it takes tables of any number
    of rows, at the width of the buffers it is given) compiled for the v5e on
    full-size shapes.  Returns the compiled step and its whole-table copies."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from multiverso_tpu.apps import SkipGram

    mv.init(args=["-updater_type=sgd", "-sync=false", "-log_level=error"],
            mesh=Mesh(np.asarray(jax.devices()[:1]), ("worker",)))
    app = SkipGram(64, DIM, learning_rate=1.0)
    assert app.table_in.stored_cols == STORED
    step, _ = app.make_fused_step()

    def spec(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    table, ids = spec(jnp.float32, ROWS, cols), spec(jnp.int32, BATCH)
    compiled = step.lower(table, (), table, (), ids, ids,
                          spec(jnp.int32, BATCH, NEG)).compile()
    copies = re.findall(rf"f32\[{ROWS},{cols}\]\S* copy\(",
                        compiled.as_text())
    return compiled, copies


def test_stored_width_compiles_without_whole_table_copies(
        mv, one_chip, no_compile_cache):
    compiled, copies = compile_sgns_step(mv, one_chip, STORED)
    assert not copies
    (din, _, dout, _, _, _, _), _ = compiled.input_formats
    out_in, _, out_out, _, _ = compiled.output_formats
    assert din.layout.major_to_minor == (0, 1)          # rows contiguous
    assert (out_in, out_out) == (din, dout)             # donation aliases
    peak = compiled.memory_analysis().peak_memory_in_bytes
    assert peak <= 2 * ROWS * STORED * 4 + 2 ** 29, peak / 2 ** 30


def test_published_width_unpadded_still_copies_the_tables(
        mv, one_chip, no_compile_cache):
    """The same step on buffers of the bare width holds the copies the
    padding removes: the backend's default layout for ``f32[3000000,300]``
    is column-major.  If that default ever becomes row-major this fails, and
    says the padding is moot."""
    compiled, copies = compile_sgns_step(mv, one_chip, DIM)
    (din, _, _, _, _, _, _), _ = compiled.input_formats
    assert din.layout.major_to_minor == (1, 0)
    assert len(copies) >= 2


# -------------------------------------------------------- the contract, CPU

@pytest.mark.parametrize("cols, stored", [(3, 3), (127, 127), (128, 128),
                                          (DIM, STORED), (384, 384),
                                          (385, 512)])
def test_only_a_table_of_a_lane_or_wider_is_padded(mv, cols, stored):
    """3 columns would rest in 128; from a lane up the padding is under
    twice the bytes."""
    mv.init(updater_type="sgd")
    table = mv.MatrixTable(16, cols)
    assert table.stored_cols == stored
    assert table.raw_value()[0].shape == (16, stored)


class Plain:
    """What the table must equal: a numpy array under plain SGD."""

    def __init__(self, init, lr):
        self.w, self.lr = init.astype(np.float32).copy(), lr

    def add(self, delta):
        self.w -= self.lr * delta

    def add_rows(self, rows, delta):
        np.subtract.at(self.w, rows, self.lr * delta)


def _dense(table, plain, rng):
    delta = rng.rand(*plain.w.shape).astype(np.float32)
    table.add(delta, sync=True)
    plain.add(delta)


def _dense_device(table, plain, rng):
    import jax.numpy as jnp

    delta = rng.rand(*plain.w.shape).astype(np.float32)
    table.add(jnp.asarray(delta), sync=True)
    plain.add(delta)


def _rows(table, plain, rng):
    rows = np.array([3, 17, 3, 39])
    delta = rng.rand(rows.size, plain.w.shape[1]).astype(np.float32)
    table.add_rows(rows, delta, sync=True)
    plain.add_rows(rows, delta)


def _store_load(table, plain, rng):
    snap = table.store_state()
    assert snap["data"].shape == plain.w.shape
    assert snap["data"].flags["C_CONTIGUOUS"]
    _rows(table, Plain(plain.w, plain.lr), rng)     # then forgotten
    table.load_state(snap)


@pytest.mark.parametrize("table_type", ["MatrixTable", "SparseMatrixTable"])
@pytest.mark.parametrize("op", [_dense, _dense_device, _rows, _store_load],
                         ids=lambda f: f.__name__.strip("_"))
def test_eager_ops_speak_num_cols_and_keep_the_padding_zero(mv, op,
                                                           table_type):
    mv.init(updater_type="sgd")
    rng = np.random.RandomState(0)
    init = rng.rand(40, DIM).astype(np.float32)
    table = getattr(mv, table_type)(
        40, DIM, init=init, default_option=mv.AddOption(learning_rate=0.5))
    plain = Plain(init, 0.5)
    for _ in range(2):
        op(table, plain, rng)
    ids = np.array([39, 3, 17, 0])
    np.testing.assert_allclose(table.get(), plain.w, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(table.get_rows(ids), plain.w[ids], rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(table.get(device=True)), plain.w,
                               rtol=1e-5, atol=1e-6)
    assert table.get().shape == (40, DIM) and table.get().flags["C_CONTIGUOUS"]
    data, _ = table.raw_value()
    assert data.shape == (40, STORED)
    assert not np.asarray(data)[:, DIM:].any()


def test_compressed_add_and_a_stateful_updater_keep_the_padding_zero(mv):
    mv.init(updater_type="adagrad")
    rng = np.random.RandomState(1)
    table = mv.MatrixTable(40, DIM, init=rng.rand(40, DIM))
    before = table.get()
    table.add(rng.rand(40, DIM).astype(np.float32), compress="1bit",
              sync=True)
    table.add_rows([5, 6], rng.rand(2, DIM).astype(np.float32), sync=True)
    assert table.get().shape == (40, DIM)
    assert not np.array_equal(table.get(), before)
    snap = table.store_state()
    assert [s.shape for s in snap["state"]] == [(40, DIM)] * len(snap["state"])
    table.load_state(snap)
    np.testing.assert_array_equal(table.get(), snap["data"])
    data, state = table.raw_value()
    assert len(state) == table.updater.num_slots >= 1
    for buf in (data, *state):
        assert buf.shape == (40, STORED)
        assert not np.asarray(buf)[:, DIM:].any()


def test_bsp_flush_lands_at_the_stored_width(mv):
    mv.init(updater_type="sgd", sync=True)
    table = mv.MatrixTable(8, DIM,
                           default_option=mv.AddOption(learning_rate=1.0))
    table.add(np.ones((8, DIM), np.float32))
    table.add_rows([2], np.ones((1, DIM), np.float32))
    assert not table.get().any()
    mv.barrier()
    want = -np.ones((8, DIM), np.float32)
    want[2] -= 1
    np.testing.assert_array_equal(table.get(), want)


def _w2v(mv):
    from multiverso_tpu.apps import SkipGram, synthetic_corpus

    app = SkipGram(64, DIM, window=3, negatives=4, learning_rate=0.1)
    corpus = synthetic_corpus(1200, 64, seed=1)
    return (app, [app.table_in, app.table_out],
            lambda seed: app.train_epoch_fused(corpus, 256, seed=seed))


def _sgmix(mv):
    from multiverso_tpu.apps import SkipGramMixture, synthetic_homonym_corpus

    app = SkipGramMixture(21, dim=DIM, senses=2, learning_rate=0.3,
                          negatives=3, window=3, seed=3)
    corpus = synthetic_homonym_corpus(1200, vocab_size=21,
                                      groups=((1, 10), (11, 20)), seed=0)
    return (app, [app.table_sense, app.table_out, app.table_prior],
            lambda seed: app.train_epoch_fused(corpus, 256, seed=seed))


@pytest.mark.parametrize("make", [_w2v, _sgmix],
                         ids=lambda f: f.__name__.strip("_"))
def test_fused_epochs_keep_the_padding_zero_and_the_program(mv, make):
    mv.init(updater_type="sgd")
    app, tables, epoch = make(mv)
    first = [t.get() for t in tables]
    epoch(1)
    step, _ = app.make_fused_step()
    programs = step._cache_size()
    epoch(2)
    assert step._cache_size() == programs   # handed back as given: no compile
    for table, before in zip(tables, first):
        data, _ = table.raw_value()
        assert data.shape[1] == table.stored_cols
        assert not np.asarray(data)[:, table.num_cols:].any()
        assert not np.array_equal(table.get(), before)       # it trained


def test_w2v_fused_step_matches_the_parity_path_at_the_published_width(mv):
    """The fused step on padded buffers and the eager Get/Add loop (which
    never sees the padding) train the same numbers."""
    mv.init(updater_type="sgd")
    from multiverso_tpu.apps import SkipGram

    kw = dict(vocab_size=50, dim=DIM, window=2, negatives=3,
              learning_rate=0.2, seed=5)
    a, b = SkipGram(name="w2v_a", **kw), SkipGram(name="w2v_b", **kw)
    rng = np.random.RandomState(0)
    c = rng.randint(50, size=32).astype(np.int32)
    o = rng.randint(50, size=32).astype(np.int32)
    neg = rng.randint(50, size=(32, 3)).astype(np.int32)
    a.train_batch(c, o, neg)
    step, place = b.make_fused_step()
    out = step(*b.table_in.raw_value(), *b.table_out.raw_value(),
               place(c), place(o), place(neg))
    b.table_in.raw_assign(*out[0:2])
    b.table_out.raw_assign(*out[2:4])
    for ta, tb in ((a.table_in, b.table_in), (a.table_out, b.table_out)):
        np.testing.assert_allclose(ta.get(), tb.get(), rtol=1e-5, atol=1e-7)


def test_lightlda_runs_at_a_topic_count_off_the_lane_multiple(mv):
    """K = 130 topics: the word-topic table stores 256 columns, both
    compiled sweeps see 130."""
    mv.init()
    from multiverso_tpu.apps import LightLDA

    docs = np.random.RandomState(0).randint(30, size=(16, 12)).astype(
        np.int32)
    lda = LightLDA(30, 130)
    assert lda.word_topic.stored_cols == 256
    doc_topic = lda.initialize_counts(docs, seed=0)
    doc_topic = lda.run_fused_pass(docs, doc_topic)
    doc_topic = lda.run_mh_pass(docs, doc_topic)
    wt = lda.word_topic.get()
    assert wt.shape == (30, 130) and wt.sum() == docs.size
    assert np.asarray(doc_topic).sum() == docs.size
    assert not np.asarray(lda.word_topic.raw_value()[0])[:, 130:].any()
