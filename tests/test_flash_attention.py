"""Pallas flash-attention kernel tests (interpret mode on the CPU mesh;
the same kernel compiles for real on TPU — see ops/flash_attention.py)."""

import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import dense_attention_ref

from multiverso_tpu import metrics
from multiverso_tpu.ops import flash_attention

fa = importlib.import_module("multiverso_tpu.ops.flash_attention")


def _bwd_traced(path):
    return metrics.counter("attention.bwd_traced", {"path": path}).value


@pytest.fixture(params=["fused", "split"])
def bwd_path(request, monkeypatch):
    """Both placements of the backward's accumulators: the fused call the
    shapes below take by the rule, and the dq and dkv kernels, which a
    budget that nothing fits sends every shape to."""
    if request.param == "split":
        monkeypatch.setattr(fa, "_FUSED_RESIDENT_BYTES", -1)
    before = _bwd_traced(request.param)
    yield request.param
    assert _bwd_traced(request.param) > before


def _fwd_traced(grid):
    return metrics.counter("attention.fwd_traced", {"grid": grid}).value


# The causal forward's grid by shape (``fa._fwd_grid``): folded where num_q
# is even and block_k a multiple of block_q, else every k block with the K/V
# index clamped; not causal, every block.
@pytest.mark.parametrize("B,H,T,D,bq,bk", [
    (2, 2, 256, 64, 128, 128),          # folded, num_q = 2
    (1, 4, 128, 32, 64, 32),            # clamped: block_q > block_k
    (2, 1, 64, 64, 64, 64),             # clamped: num_q = 1
    (1, 2, 256, 128, 256, 64),
    (1, 2, 512, 32, 64, 128),           # folded, r = 2, num_q = 8
    (1, 2, 256, 32, 64, 64),            # folded, r = 1
    (1, 1, 256, 32, 32, 128),           # folded, r = 4
    (1, 1, 1536, 32, 512, 1024),        # clamped: blocks 512 x 512, num_q = 3
])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_matches_dense(B, H, T, D, bq, bk, causal):
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32))
    k = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32))
    v = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32))
    want = dense_attention_ref(q, k, v, causal)
    got = flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5)


def test_flash_rejects_misaligned():
    # No power-of-two block >= 8 divides 100: unusable, so it raises.
    q = jnp.zeros((1, 1, 100, 32))
    with pytest.raises(ValueError, match="no usable block"):
        flash_attention(q, q, q, block_q=64, block_k=64, interpret=True)


def test_flash_block_fallback_fits_odd_lengths():
    """Requested blocks shrink to the largest dividing power of two —
    T=192 runs under the 512/1024 defaults (as 64-blocks) instead of
    raising like rounds 1-3 did."""
    rng = np.random.RandomState(3)
    q = jnp.asarray(rng.randn(1, 2, 192, 32).astype(np.float32)) * 0.3
    k = jnp.asarray(rng.randn(1, 2, 192, 32).astype(np.float32)) * 0.3
    v = jnp.asarray(rng.randn(1, 2, 192, 32).astype(np.float32)) * 0.3
    got = flash_attention(q, k, v, causal=True, interpret=True)
    want = dense_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5)


def test_local_attention_cpu_fallback_is_jnp():
    """On the CPU backend the dispatcher must not take the Pallas path."""
    from multiverso_tpu.parallel.ring_attention import blockwise_attention_local

    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(1, 2, 128, 32).astype(np.float32))
    got = blockwise_attention_local(q, q, q, 32 ** -0.5)
    want = dense_attention_ref(q, q, q)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


# ---------------------------------------------------------------------------
# Gradient coverage (round-1 verdict: the missing tests that would have
# caught the non-differentiable kernel voiding the TPU bench).
# ---------------------------------------------------------------------------

def _dense_loss(q, k, v, causal):
    return jnp.sum(jnp.square(dense_attention_ref(q, k, v, causal)))


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("T,bq,bk", [
    (128, 64, 64), (256, 128, 128),
    (512, 64, 128),                     # folded, r = 2
    (384, 128, 128),                    # clamped, num_q = 3
])
def test_flash_grad_matches_dense(causal, T, bq, bk, bwd_path):
    rng = np.random.RandomState(2)
    B, H, D = 1, 2, 32
    q = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32)) * 0.3
    k = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32)) * 0.3
    v = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32)) * 0.3

    def flash_loss(q, k, v):
        o = flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk,
                            interpret=True)
        return jnp.sum(jnp.square(o))

    gq, gk, gv = jax.grad(flash_loss, argnums=(0, 1, 2))(q, k, v)
    wq, wk, wv = jax.grad(_dense_loss, argnums=(0, 1, 2))(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(gq), np.asarray(wq), atol=2e-4)
    np.testing.assert_allclose(np.asarray(gk), np.asarray(wk), atol=2e-4)
    np.testing.assert_allclose(np.asarray(gv), np.asarray(wv), atol=2e-4)


def test_flash_lse_matches_dense():
    rng = np.random.RandomState(3)
    q = jnp.asarray(rng.randn(1, 2, 128, 32).astype(np.float32))
    k = jnp.asarray(rng.randn(1, 2, 128, 32).astype(np.float32))
    v = jnp.asarray(rng.randn(1, 2, 128, 32).astype(np.float32))
    scale = 32 ** -0.5
    _, lse = flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                             interpret=True, return_lse=True)
    s = jnp.einsum("bhtd,bhsd->bhts", q, k) * scale
    mask = jnp.tril(jnp.ones((128, 128), bool))
    s = jnp.where(mask[None, None], s, -jnp.inf)
    want = jax.nn.logsumexp(s, axis=-1)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(want), atol=2e-5)


def test_flash_lse_combination_rule(bwd_path):
    """Two normalized partials combined via lse == attention over the
    concatenated keys — the identity the ring schedule relies on — and
    its gradient flows through the lse output's custom_vjp path."""
    rng = np.random.RandomState(4)
    B, H, T, D = 1, 1, 128, 32
    q = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32)) * 0.5
    k = jnp.asarray(rng.randn(B, H, 2 * T, D).astype(np.float32)) * 0.5
    v = jnp.asarray(rng.randn(B, H, 2 * T, D).astype(np.float32)) * 0.5

    def combined_loss(q, k, v):
        o1, l1 = flash_attention(q, k[:, :, :T], v[:, :, :T], causal=False,
                                 block_q=64, block_k=64, interpret=True,
                                 return_lse=True)
        o2, l2 = flash_attention(q, k[:, :, T:], v[:, :, T:], causal=False,
                                 block_q=64, block_k=64, interpret=True,
                                 return_lse=True)
        lse = jnp.logaddexp(l1, l2)
        o = (o1 * jnp.exp(l1 - lse)[..., None]
             + o2 * jnp.exp(l2 - lse)[..., None])
        return jnp.sum(jnp.square(o))

    def dense_loss(q, k, v):
        return jnp.sum(jnp.square(dense_attention_ref(q, k, v, causal=False)))

    got = combined_loss(q, k, v)
    want = dense_loss(q, k, v)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
    gq, gk, gv = jax.grad(combined_loss, argnums=(0, 1, 2))(q, k, v)
    wq, wk, wv = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(np.asarray(gq), np.asarray(wq), atol=3e-4)
    np.testing.assert_allclose(np.asarray(gk), np.asarray(wk), atol=3e-4)
    np.testing.assert_allclose(np.asarray(gv), np.asarray(wv), atol=3e-4)


def test_flash_grad_bf16(bwd_path):
    """bf16 inputs differentiate without error and track the f32 grads."""
    rng = np.random.RandomState(5)
    q = jnp.asarray(rng.randn(1, 2, 128, 64).astype(np.float32)) * 0.3

    def loss(x, interp_dtype):
        x = x.astype(interp_dtype)
        o = flash_attention(x, x, x, causal=True, block_q=64, block_k=64,
                            interpret=True)
        return jnp.sum(jnp.square(o.astype(jnp.float32)))

    g16 = jax.grad(lambda x: loss(x, jnp.bfloat16))(q)
    g32 = jax.grad(lambda x: loss(x, jnp.float32))(q)
    np.testing.assert_allclose(np.asarray(g16), np.asarray(g32),
                               atol=0.15, rtol=0.1)


def test_forced_flash_dispatch_under_value_and_grad(monkeypatch, bwd_path):
    """CI coverage of the exact line that killed round-1's bench: the
    dispatcher sends the transformer's attention to the Pallas kernel and
    value_and_grad must work through it."""
    from multiverso_tpu.parallel.ring_attention import (
        blockwise_attention_local)

    monkeypatch.setenv("MVTPU_FORCE_FLASH", "interpret")
    rng = np.random.RandomState(6)
    q = jnp.asarray(rng.randn(1, 2, 128, 32).astype(np.float32)) * 0.4

    def loss(x):
        o = blockwise_attention_local(x, x, x, 32 ** -0.5, causal=True)
        return jnp.sum(jnp.square(o))

    val, grad = jax.value_and_grad(loss)(q)

    def dense(x):
        return jnp.sum(jnp.square(dense_attention_ref(x, x, x, True)))

    wval, wgrad = jax.value_and_grad(dense)(q)
    np.testing.assert_allclose(float(val), float(wval), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(grad), np.asarray(wgrad),
                               atol=2e-4)


def test_forced_flash_transformer_train_step(monkeypatch):
    """Full train_step with the flash kernel force-dispatched (interpret):
    the end-to-end path the TPU bench runs."""
    monkeypatch.setenv("MVTPU_FORCE_FLASH", "interpret")
    from jax.sharding import Mesh
    from multiverso_tpu.models.transformer import (
        TransformerConfig, TransformerTrainer)

    devs = np.array(jax.devices()[:1]).reshape(1, 1, 1)
    mesh = Mesh(devs, ("dp", "sp", "tp"))
    cfg = TransformerConfig(vocab_size=64, dim=64, n_layers=1, n_heads=2,
                            hidden=128, max_seq=128,
                            compute_dtype=jnp.float32)
    tr = TransformerTrainer(cfg, mesh, updater_type="sgd")
    toks = np.random.RandomState(7).randint(0, 64, (2, 128), dtype=np.int64)
    l0 = tr.train_step(toks)
    l1 = tr.train_step(toks)
    assert np.isfinite(l0) and np.isfinite(l1)
    assert l1 < l0


# ---------------------------------------------------------------------------
# The fused backward (PR 35): one call builds a tile's s, p, dp and ds once
# and gives dq, dk and dv.  Each case against dense float32 attention and
# against the dq and dkv kernels called directly on the same residuals.
# ---------------------------------------------------------------------------

def _dense_out_lse(q, k, v, causal, window):
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    s = jnp.einsum("bhtd,bhsd->bhts", q, k) * q.shape[-1] ** -0.5
    if causal:
        t, u = jnp.arange(q.shape[2])[:, None], jnp.arange(k.shape[2])[None]
        visible = t >= u
        if window is not None:
            visible = visible & (u > t - window)
        s = jnp.where(visible, s, -jnp.inf)
    return (jnp.einsum("bhts,bhsd->bhtd", jax.nn.softmax(s, -1), v),
            jax.nn.logsumexp(s, -1))


# id: (H, KV, Tq, Tk, causal, window, bwd blocks (None: the defaults), dtype,
#      a cotangent on lse)
FUSED_CASES = {
    "causal": (2, 2, 256, 256, True, None, (128, 128), jnp.float32, False),
    "full": (2, 2, 256, 256, False, None, (128, 64), jnp.float32, False),
    "cross-length": (2, 2, 128, 384, False, None, (128, 128), jnp.float32,
                     False),
    "lse-cotangent": (2, 2, 128, 384, False, None, (128, 128), jnp.float32,
                      True),
    "lse-cotangent-causal": (2, 1, 256, 256, True, None, (128, 128),
                             jnp.float32, True),
    "group-2": (4, 2, 256, 256, True, None, (128, 64), jnp.float32, False),
    "group-6": (6, 1, 256, 256, True, None, (128, 128), jnp.float32, False),
    "window-below": (2, 2, 256, 256, True, 128, (128, 128), jnp.float32,
                     False),
    "window-equal": (2, 2, 256, 256, True, 256, (128, 128), jnp.float32,
                     False),
    "window-above": (2, 2, 256, 256, True, 512, (256, 128), jnp.float32,
                     False),
    "window-ragged": (4, 2, 256, 256, True, 100, (128, 64), jnp.float32,
                      False),
    "window-group-6": (6, 1, 512, 512, True, 72, (128, 128), jnp.float32,
                       False),
    "odd-length": (2, 2, 384, 384, True, None, None, jnp.float32, False),
    "bf16": (2, 2, 256, 256, True, None, (128, 128), jnp.bfloat16, False),
    "bf16-window-group": (4, 2, 256, 256, True, 72, (128, 128),
                          jnp.bfloat16, True),
}


@functools.lru_cache(maxsize=None)
def _fused_case(name):
    """``(fused, split, dense)`` gradients ``(dq, dk, dv)`` of one case,
    float32 numpy; computed once for the three tests that read it."""
    H, KV, Tq, Tk, causal, window, blocks, dtype, lse_ct = FUSED_CASES[name]
    rng = np.random.RandomState(len(name))
    B, D = 2, 32
    q = jnp.asarray(rng.randn(B, H, Tq, D), dtype)
    k = jnp.asarray(rng.randn(B, KV, Tk, D), dtype)
    v = jnp.asarray(rng.randn(B, KV, Tk, D), dtype)
    w = jnp.asarray(rng.randn(B, H, Tq, D), jnp.float32)
    wl = jnp.asarray(rng.randn(B, H, Tq) * float(lse_ct), jnp.float32)
    bq, bk = blocks or (None, None)

    def flash(q, k, v):
        o, lse = flash_attention(q, k, v, causal=causal, window=window,
                                 block_q=64, block_k=64, block_q_bwd=bq,
                                 block_k_bwd=bk, interpret=True,
                                 return_lse=True)
        return jnp.sum(o.astype(jnp.float32) * w) + jnp.sum(lse * wl)

    def dense(q, k, v):
        o, lse = _dense_out_lse(q, k, v, causal, window)
        return jnp.sum(o * w) + jnp.sum(lse * wl)

    before = _bwd_traced("fused"), _bwd_traced("split")
    fused = jax.grad(flash, (0, 1, 2))(q, k, v)
    assert (_bwd_traced("fused"), _bwd_traced("split")) == (
        before[0] + 1, before[1])

    # the dq and dkv kernels, called directly on the forward's residuals
    scale, group = D ** -0.5, H // KV
    bq, bk = blocks or (fa.fit_block(1024, Tq), fa.fit_block(1024, Tk))
    q3, k3, v3 = (x.reshape(-1, x.shape[2], D) for x in (q, k, v))
    o, lse = fa._fwd_impl(q3, k3, v3, scale, causal, 64, 64, True, window,
                          group)
    do = w.reshape(-1, Tq, D).astype(dtype)
    delta = (jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), -1)
             - wl.reshape(-1, Tq))
    split = fa._bwd_split(
        (q3.astype(jnp.float32) * scale).astype(dtype), k3, v3, do, lse,
        delta, scale, causal, bq, bk, True, window, group)
    split = [g.reshape(x.shape) for g, x in zip(split, (q, k, v))]
    want = jax.grad(dense, (0, 1, 2))(q, k, v)
    return tuple([np.asarray(g, np.float32) for g in gs]
                 for gs in (fused, split, want))


@pytest.mark.parametrize("grad", ["dq", "dk", "dv"])
@pytest.mark.parametrize("name", list(FUSED_CASES))
def test_fused_backward_against_dense_and_split(name, grad):
    fused, split, want = (gs[("dq", "dk", "dv").index(grad)]
                          for gs in _fused_case(name))
    bf16 = FUSED_CASES[name][7] == jnp.bfloat16
    assert fused.shape == want.shape == split.shape
    peak = np.max(np.abs(want))
    # against the reference: float32 to rounding, bfloat16 to its 2^-8
    assert np.max(np.abs(fused - want)) <= (3e-2 if bf16 else 2e-5) * peak
    # against the two kernels: the same tiles, masks, dtypes and sums
    assert np.max(np.abs(fused - split)) <= (1e-2 if bf16 else 2e-6) * peak


def test_backward_over_the_budget_takes_the_split_kernels():
    """A query head whose dq (float32 [Tq, D], and its output block) passes
    ``_FUSED_RESIDENT_BYTES`` runs the dq and dkv kernels, by the one rule
    and with no argument; the counter says which ran."""
    Tq, Tk, D = 32768, 128, 128
    assert not fa._fused_fits(Tq, Tk, D, 1, jnp.float32, 1024)
    # every cell's shape fits: the dense ones, Laguna's grouped layers
    assert fa._fused_fits(2048, 2048, D, 1, jnp.bfloat16, 1024)
    assert fa._fused_fits(8192, 8192, D, 1, jnp.bfloat16, 1024)
    assert fa._fused_fits(8192, 8192, D, 6, jnp.bfloat16, 1024)
    assert fa._fused_fits(8192, 8192, D, 9, jnp.bfloat16, 512)
    # a group holds dk and dv for the whole K/V head as well
    assert fa._fused_fits(16384, 16384, D, 1, jnp.bfloat16, 1024)
    assert not fa._fused_fits(16384, 16384, D, 2, jnp.bfloat16, 1024)
    assert not fa._fused_fits(65536, 65536, D, 1, jnp.bfloat16, 1024)
    assert not fa._fused_fits(192, 8192, D, 1, jnp.bfloat16, 64)  # half lanes
    rng = np.random.RandomState(11)
    q = jnp.asarray(rng.randn(1, 1, Tq, D), jnp.float32) * 0.3
    k = jnp.asarray(rng.randn(1, 1, Tk, D), jnp.float32) * 0.3
    v = jnp.asarray(rng.randn(1, 1, Tk, D), jnp.float32) * 0.3

    def flash(q, k, v):
        return jnp.sum(jnp.square(flash_attention(
            q, k, v, causal=False, interpret=True)))

    before = _bwd_traced("fused"), _bwd_traced("split")
    got = jax.grad(flash, (0, 1, 2))(q, k, v)
    assert (_bwd_traced("fused"), _bwd_traced("split")) == (
        before[0], before[1] + 1)
    text = str(jax.make_jaxpr(jax.grad(flash, (0, 1, 2)))(q, k, v))
    assert "flash_bwd_dq" in text and "flash_bwd_dkv" in text
    want = jax.grad(_dense_loss, (0, 1, 2))(q, k, v, False)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=2e-5)


# ---------------------------------------------------------------------------
# The causal forward's grid (PR 51): a step only where there is a tile.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("num_q,r", [
    (num_q, r) for r in (1, 2, 4) for num_q in range(2, 33, 2)
    if num_q % r == 0])
def test_folded_schedule_visits_each_tile_once(num_q, r):
    """``_fold_step`` on the host: row p's ``num_k + 1`` steps are q block
    p's k blocks and then q block ``num_q - 1 - p``'s.  Every computed (q
    block, k block) exactly once, k ascending within a q block, a q block's
    steps consecutive with ``first`` / ``last`` on their ends, and no step
    without a tile."""
    num_k = num_q // r
    p, j = np.meshgrid(np.arange(num_q // 2), np.arange(num_k + 1),
                       indexing="ij")
    qi, ki, first, last = (np.asarray(x).ravel()      # in the grid's order
                           for x in fa._fold_step(p, j, num_q, r))
    assert np.all((0 <= ki) & (ki <= qi // r))        # a tile on every step
    want = {(a, b) for a in range(num_q) for b in range(a // r + 1)}
    assert len(qi) == len(want)
    assert set(zip(qi.tolist(), ki.tolist())) == want
    # q blocks in runs of consecutive steps, each run k = 0, 1, .. in order
    starts = np.flatnonzero(np.r_[True, qi[1:] != qi[:-1]])
    assert len(starts) == num_q == len(set(qi[starts].tolist()))
    for lo, hi in zip(starts, np.r_[starts[1:], len(qi)]):
        assert ki[lo:hi].tolist() == list(range(hi - lo))
    assert np.flatnonzero(first).tolist() == starts.tolist()
    assert np.flatnonzero(last).tolist() == (
        np.r_[starts[1:], len(qi)] - 1).tolist()
    # the call's grid and index maps are that schedule's
    kind, fold, grid, q_index, kv_index = fa._fwd_plan(
        True, None, num_q, num_k, 64, 64 * r)
    assert (kind, fold, grid) == ("folded", (num_q, r),
                                  (num_q // 2, num_k + 1))
    assert np.asarray(q_index(5, p, j)[1]).ravel().tolist() == qi.tolist()
    assert np.asarray(kv_index(2)(5, p, j)[1]).ravel().tolist() == ki.tolist()
    assert (q_index(5, 0, 0)[0], kv_index(2)(5, 0, 0)[0]) == (5, 2)


@pytest.mark.parametrize("num_q,block_q,block_k", [
    (3, 512, 512), (1, 64, 64), (5, 16, 16), (4, 64, 32), (8, 256, 64),
    (7, 32, 64)])
def test_clamped_grid_fetches_no_block_it_skips(num_q, block_q, block_k):
    """A causal call the fold does not take keeps the grid ``(q block, every
    k block)``; the K/V index of a step past the diagonal is the row's last
    computed block's, so the pipeline fetches nothing for it."""
    T = num_q * block_q
    num_k = -(-T // block_k)
    assert fa._fwd_grid(True, None, num_q, block_q, block_k) == "clamped"
    index = fa._kv_index(block_q, block_k, None, 1, causal=True)
    for i in range(num_q):
        last = (i * block_q + block_q - 1) // block_k
        got = [int(index(0, i, j)[1]) for j in range(num_k)]
        assert got == [min(j, last) for j in range(num_k)]
    # not causal: every block is computed, and fetched
    index = fa._kv_index(block_q, block_k, None, 1, causal=False)
    assert [int(index(0, 0, j)[1]) for j in range(num_k)] == list(range(num_k))


def test_the_grid_is_decided_by_the_shapes():
    # every cell's causal forward (2,048 to 16,384 tokens at the dispatch's
    # 512 x 1024 blocks) is folded
    for T in (2048, 4096, 8192, 16384):
        bq, bk = fa.fit_block(512, T), fa.fit_block(1024, T)
        assert fa._fwd_grid(True, None, T // bq, bq, bk) == "folded"
    assert fa._fwd_grid(True, None, 2, 128, 128) == "folded"
    assert fa._fwd_grid(True, None, 3, 512, 512) == "clamped"    # T = 1,536
    assert fa._fwd_grid(True, None, 1, 64, 64) == "clamped"
    assert fa._fwd_grid(True, None, 4, 64, 32) == "clamped"
    assert fa._fwd_grid(False, None, 16, 512, 1024) == "full"
    assert fa._fwd_grid(True, 512, 16, 512, 512) == "band"


# id: (grid, H, KV, T, block_q, block_k, causal, window, dtype)
GRID_CASES = {
    "folded-r2": ("folded", 2, 2, 512, 64, 128, True, None, jnp.float32),
    "folded-r1": ("folded", 2, 2, 256, 64, 64, True, None, jnp.float32),
    "folded-r4": ("folded", 1, 1, 256, 32, 128, True, None, jnp.float32),
    "folded-num_q-2": ("folded", 2, 2, 128, 64, 128, True, None,
                       jnp.float32),
    "folded-group-2": ("folded", 4, 2, 512, 64, 128, True, None,
                       jnp.float32),
    "folded-group-7": ("folded", 7, 1, 256, 32, 64, True, None, jnp.float32),
    "folded-bf16": ("folded", 4, 2, 512, 64, 128, True, None, jnp.bfloat16),
    "clamped-odd": ("clamped", 2, 2, 1536, 512, 1024, True, None,
                    jnp.float32),
    "clamped-odd-group": ("clamped", 4, 2, 384, 128, 128, True, None,
                          jnp.float32),
    "clamped-num_q-1": ("clamped", 2, 2, 64, 64, 64, True, None,
                        jnp.float32),
    "clamped-wide-q": ("clamped", 2, 1, 256, 128, 32, True, None,
                       jnp.float32),
    "full": ("full", 2, 2, 256, 64, 128, False, None, jnp.float32),
    "band": ("band", 4, 2, 256, 64, 64, True, 100, jnp.float32),
}


@pytest.mark.parametrize("name", list(GRID_CASES))
def test_each_forward_grid_matches_dense_and_is_counted(name):
    """``o`` and ``lse`` (``return_lse``) of every grid the forward can take
    against dense float32 attention; one trace counts its grid once in
    ``attention.fwd_traced{grid=}`` and no other."""
    grid, H, KV, T, bq, bk, causal, window, dtype = GRID_CASES[name]
    rng = np.random.RandomState(len(name))
    q = jnp.asarray(rng.randn(1, H, T, 32), dtype)
    k = jnp.asarray(rng.randn(1, KV, T, 32), dtype)
    v = jnp.asarray(rng.randn(1, KV, T, 32), dtype)
    kinds = ("folded", "clamped", "full", "band")
    before = [_fwd_traced(g) for g in kinds]
    o, lse = flash_attention(q, k, v, causal=causal, window=window,
                             block_q=bq, block_k=bk, interpret=True,
                             return_lse=True)
    assert [_fwd_traced(g) - b for g, b in zip(kinds, before)] == [
        float(g == grid) for g in kinds]
    want_o, want_lse = _dense_out_lse(q, k, v, causal, window)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(want_o), atol=tol)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(want_lse),
                               atol=tol)


def _dense_latent(qn, qr, kn, kr, v, scale):
    T = qn.shape[2]
    s = (jnp.einsum("bhtd,bhsd->bhts", qn, kn)
         + jnp.einsum("bhtd,bsd->bhts", qr, kr[:, 0])) * scale
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    return (jnp.einsum("bhts,bhsd->bhtd", jax.nn.softmax(s, axis=-1), v),
            jax.scipy.special.logsumexp(s, axis=-1))


@pytest.mark.parametrize("grid,T,bq,bk", [
    ("folded", 512, 64, 128), ("folded", 256, 64, 64),
    ("clamped", 384, 128, 128), ("clamped", 64, 64, 64)],
    ids=["folded-r2", "folded-r1", "clamped-odd", "clamped-num_q-1"])
def test_latent_forward_grids_match_dense_and_are_counted(grid, T, bq, bk):
    """The two-width forward takes the same grids by the same rule: o and
    lse below the wrapper (which drops lse), and the five gradients through
    it, against dense attention (tests/test_xing.py's tolerance)."""
    from multiverso_tpu.ops.flash_attention import flash_attention_latent

    rng = np.random.RandomState(T)
    B, H, dn, dr, dv = 1, 3, 16, 8, 24
    scale = 0.3

    def draw(*shape):
        return jnp.asarray(rng.randn(*shape).astype(np.float32))

    args = (draw(B, H, T, dn), draw(B, H, T, dr), draw(B, H, T, dn),
            draw(B, 1, T, dr), draw(B, H, T, dv))
    weight = draw(B, H, T, dv)

    def rel(a, b):
        return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))

    with jax.default_matmul_precision("highest"):
        before = _fwd_traced("folded"), _fwd_traced("clamped")
        flat = [a.reshape(-1, T, a.shape[-1]) for a in args]
        o, lse = fa._mla_fwd_impl(*flat, scale, H, bq, bk, True)
        assert (_fwd_traced("folded") - before[0],
                _fwd_traced("clamped") - before[1]) == (
                    float(grid == "folded"), float(grid == "clamped"))
        want_o, want_lse = _dense_latent(*args, scale)
        assert rel(o.reshape(want_o.shape), want_o) < 1e-5
        assert rel(lse.reshape(want_lse.shape), want_lse) < 1e-5
        got = jax.grad(lambda *a: jnp.sum(flash_attention_latent(
            *a, scale=scale, block_q=bq, block_k=bk, block_q_bwd=bq,
            block_k_bwd=bk, interpret=True) * weight), argnums=range(5))(*args)
        want = jax.grad(lambda *a: jnp.sum(_dense_latent(*a, scale)[0]
                                           * weight), argnums=range(5))(*args)
    for g, w, a in zip(got, want, args):
        assert g.shape == a.shape and rel(g, w) < 1e-5
