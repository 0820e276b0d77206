"""Updater math tests — dense and row-sparse paths, all five updaters.

Models the reference's updater unit tests; the math is checked against
closed-form numpy (reference src/updater/*.cpp semantics, SURVEY.md §2.16).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from multiverso_tpu.updaters import AddOption, get_updater, updater_names


OPT = AddOption(learning_rate=0.1, momentum=0.9, rho=0.5, eps=1e-8)


def test_registry_names():
    names = updater_names()
    for n in ("default", "add", "sgd", "adagrad", "momentum",
              "smooth_gradient"):
        assert n in names
    with pytest.raises(ValueError):
        get_updater("nope")


def _dense(name, w, d, steps=1):
    u = get_updater(name)
    s = u.init_state(w.shape, w.dtype)
    w = jnp.asarray(w)
    for _ in range(steps):
        w, s = u.apply_dense(w, s, jnp.asarray(d), OPT)
    return np.asarray(w), [np.asarray(x) for x in s]


def test_default_add():
    w = np.ones(4, np.float32)
    d = np.full(4, 2.0, np.float32)
    out, _ = _dense("default", w, d)
    np.testing.assert_allclose(out, 3.0)


def test_sgd():
    w = np.ones(4, np.float32)
    g = np.full(4, 2.0, np.float32)
    out, _ = _dense("sgd", w, g)
    np.testing.assert_allclose(out, 1.0 - 0.1 * 2.0, rtol=1e-6)


def test_adagrad_two_steps():
    w = np.zeros(3, np.float32)
    g = np.ones(3, np.float32)
    out, (h,) = _dense("adagrad", w, g, steps=2)
    # step1: h=1, w=-0.1/1 ; step2: h=2, w-=0.1/sqrt(2)
    exp = -0.1 - 0.1 / np.sqrt(2.0)
    np.testing.assert_allclose(out, exp, rtol=1e-5)
    np.testing.assert_allclose(h, 2.0, rtol=1e-6)


def test_momentum_two_steps():
    w = np.zeros(3, np.float32)
    g = np.ones(3, np.float32)
    out, (v,) = _dense("momentum", w, g, steps=2)
    # v1=0.1, w1=-0.1; v2=0.9*0.1+0.1=0.19, w2=-0.29
    np.testing.assert_allclose(v, 0.19, rtol=1e-6)
    np.testing.assert_allclose(out, -0.29, rtol=1e-6)


def test_smooth_gradient_two_steps():
    w = np.zeros(3, np.float32)
    g = np.ones(3, np.float32)
    out, (s,) = _dense("smooth_gradient", w, g, steps=2)
    # s1=0.5, w1=-0.05; s2=0.5*0.5+0.5=0.75, w2=-0.05-0.075=-0.125
    np.testing.assert_allclose(s, 0.75, rtol=1e-6)
    np.testing.assert_allclose(out, -0.125, rtol=1e-6)


@pytest.mark.parametrize("name", ["sgd", "adagrad", "momentum",
                                  "smooth_gradient", "default"])
def test_rows_matches_dense_on_unique_rows(name):
    """Scatter path == dense path when every row is touched exactly once."""
    rng = np.random.RandomState(0)
    w0 = rng.randn(6, 4).astype(np.float32)
    g = rng.randn(6, 4).astype(np.float32)

    u = get_updater(name)
    s0 = u.init_state(w0.shape, jnp.float32)
    wd, sd = u.apply_dense(jnp.asarray(w0), s0, jnp.asarray(g), OPT)

    rows = jnp.arange(6, dtype=jnp.int32)
    ws, ss = u.apply_rows(jnp.asarray(w0), s0, rows, jnp.asarray(g), OPT)

    np.testing.assert_allclose(np.asarray(wd), np.asarray(ws), rtol=1e-5)
    for a, b in zip(sd, ss):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5)


@pytest.mark.parametrize("name", ["sgd", "adagrad", "momentum",
                                  "smooth_gradient", "default"])
def test_rows_padding_dropped(name):
    """Padding entries (OOB row or mask=False) must not touch any row."""
    w0 = np.ones((4, 2), np.float32)
    u = get_updater(name)
    s0 = u.init_state(w0.shape, jnp.float32)

    rows = jnp.asarray([1, 4, 0], dtype=jnp.int32)   # 4 = OOB pad
    delta = jnp.ones((3, 2), dtype=jnp.float32) * 5.0
    mask = jnp.asarray([True, False, False])          # entry 2 masked off

    w1, s1 = u.apply_rows(jnp.asarray(w0), s0, rows, delta, OPT, mask=mask)
    w1 = np.asarray(w1)
    # row 0 masked off → unchanged; rows 2,3 untouched
    np.testing.assert_allclose(w1[0], w0[0])
    np.testing.assert_allclose(w1[2:], w0[2:])
    # row 1 changed
    assert not np.allclose(w1[1], w0[1])
    for st in s1:
        st = np.asarray(st)
        np.testing.assert_allclose(st[0], 0.0)
        np.testing.assert_allclose(st[2:], 0.0)


# -------------------------------------------------- scatter_apply, in a jit

def _ids(pattern, n, rows, rng):
    """Row ids of a batch: ``n`` of them over ``rows`` rows."""
    if pattern == "distinct":
        return rng.permutation(rows)[:n]
    if pattern == "one_id":
        return np.full(n, 77)
    if pattern == "zipf":
        return (rng.zipf(1.1, n) - 1) % rows
    if pattern == "boundary_runs":
        # Sorted, the kernel cuts the batch into blocks of 256 ids.  Runs of
        # one row and of one group of 8 rows laid across the cuts at 256,
        # 512 and 768, one of them longer than a block.
        ids = np.concatenate([
            np.arange(0, 250 * 8, 8),             # 250 groups, one id each
            np.full(16, 3000),                    # one row over the first cut
            np.arange(3008, 3008 + 100),          # whole groups, 8 ids each
            np.full(300, 5000),                   # a run longer than a block
            5008 + np.arange(40) % 8,             # one group, all its rows
            np.arange(6000, 6000 + 318 * 3, 3)])  # runs of 2 or 3 ids
        assert ids.shape[0] == n == 1024
        return rng.permutation(ids)               # the batch comes unsorted
    if pattern == "dropped":
        # Out of range ids are dropped; negative ones count from the end.
        ids = rng.integers(0, rows, n)
        ids[::7] = rows + 3
        ids[1::7] = -ids[1::7] - 1
        ids[2::50] = -rows - 5
        return ids
    raise AssertionError(pattern)


def _applied(updater, path, pattern, cols, n=1024, rows=8192):
    """``scatter_apply`` under ``jit`` against ``np.add.at``; every number
    is a small integer (and the learning rate a power of two), so the sums
    are exact in float32 whatever their order."""
    from multiverso_tpu import metrics
    from multiverso_tpu.updaters.base import scatter_apply

    rng = np.random.default_rng(31)
    ids = _ids(pattern, n, rows, rng).astype(np.int32)
    w0 = rng.integers(-5, 6, (rows, cols)).astype(np.float32)
    delta = rng.integers(-3, 4, (n, cols)).astype(np.float32)
    upd, opt = get_updater(updater), AddOption(learning_rate=0.5)
    counter = metrics.counter("tables.scatter_traced", {"path": path})
    before = counter.value
    step = jax.jit(lambda w, r, d: scatter_apply(upd, w, (), r, d, opt))
    got, state = step(jnp.asarray(w0), jnp.asarray(ids), jnp.asarray(delta))
    assert state == () and counter.value == before + 1

    want = w0.copy()
    ids = np.where(ids < 0, ids + rows, ids)
    keep = (ids >= 0) & (ids < rows)
    np.add.at(want, ids[keep],
              (-0.5 * delta if updater == "sgd" else delta)[keep])
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("updater", ["sgd", "default"])
@pytest.mark.parametrize("pattern", ["distinct", "one_id", "zipf",
                                     "boundary_runs", "dropped"])
@pytest.mark.parametrize("cols, kernel", [
    (384, "one_call"),  # the row-update kernel, in interpret mode here
    (384, "calls"),     # a batch of more ids than one call of it takes
    (384, None),        # what every backend but the TPU runs
    (96, "one_call"),   # rows no whole number of lanes wide: XLA's scatter
])
def test_scatter_apply_linear_equals_add_at(monkeypatch, mv, updater,
                                            pattern, cols, kernel):
    # ``mv``: no runtime is up, so no mesh of several devices is in sight.
    from multiverso_tpu.ops import row_update
    from multiverso_tpu.updaters import base

    if kernel:
        monkeypatch.setattr(base, "_row_kernel", lambda: True)
    if kernel == "calls":
        monkeypatch.setattr(row_update, "_MAX_IDS", 384)
    path = "kernel" if kernel and cols % 128 == 0 else "xla"
    _applied(updater, path, pattern, cols)


@pytest.mark.parametrize("why", ["batch_not_in_eights", "rows_not_in_eights",
                                 "several_devices", "own_apply_rows",
                                 "not_linear"])
def test_scatter_apply_keeps_xla_where_the_kernel_cannot_go(
        monkeypatch, mv, why):
    from multiverso_tpu import metrics
    from multiverso_tpu.updaters import base

    monkeypatch.setattr(base, "_row_kernel", lambda: True)
    upd, n, rows = get_updater("sgd"), 64, 256
    if why == "batch_not_in_eights":
        n = 60
    elif why == "rows_not_in_eights":
        rows = 250
    elif why == "several_devices":
        from multiverso_tpu.core import context

        mv.init(updater_type="sgd")
        assert context.get_context().mesh.size > 1
    elif why == "own_apply_rows":
        class Own(base.Updater):
            def apply_rows(self, w, state, rows, delta, opt, mask=None):
                return w.at[rows].add(2 * delta, mode="drop"), state
        upd = Own()
    else:
        upd = get_updater("adagrad")
    path = "aggregated" if why == "not_linear" else "xla"
    counter = metrics.counter("tables.scatter_traced", {"path": path})
    kernel = metrics.counter("tables.scatter_traced", {"path": "kernel"})
    before = counter.value, kernel.value
    state = upd.init_state((rows, 128), jnp.float32)
    jax.eval_shape(
        lambda w, s, r, d: base.scatter_apply(upd, w, s, r, d, OPT),
        jnp.zeros((rows, 128)), state, jnp.zeros(n, jnp.int32),
        jnp.zeros((n, 128)))
    assert (counter.value, kernel.value) == (before[0] + 1, before[1])
