"""Flagship transformer + ring attention tests: exactness of the
sequence-parallel path against the local path, sharded training
convergence, and updater-semantics integration."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import dense_attention_ref
from jax.sharding import Mesh

from multiverso_tpu.models import (TransformerConfig, TransformerTrainer,
                                   init_params)
from multiverso_tpu.models.transformer import lm_loss, transformer_forward
from multiverso_tpu.parallel.ring_attention import (
    blockwise_attention_local, ring_attention)


@pytest.fixture(scope="module")
def qkv():
    rng = np.random.RandomState(0)
    mk = lambda: jnp.asarray(rng.randn(2, 4, 64, 16).astype(np.float32))
    return mk(), mk(), mk()


def test_blockwise_local_matches_dense(qkv):
    q, k, v = qkv
    want = dense_attention_ref(q, k, v)
    got = blockwise_attention_local(q, k, v, 16 ** -0.5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5)


@pytest.mark.parametrize("shape,names", [
    ((8,), ("sp",)),
    ((2, 4), ("dp", "sp")),
    ((2, 2, 2), ("dp", "sp", "tp")),
])
def test_ring_attention_exact(qkv, shape, names):
    q, k, v = qkv
    mesh = Mesh(np.asarray(jax.devices()).reshape(shape), names)
    want = dense_attention_ref(q, k, v)
    got = ring_attention(q, k, v, mesh)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)


def test_ring_attention_non_causal(qkv):
    q, k, v = qkv
    mesh = Mesh(np.asarray(jax.devices()).reshape(2, 4), ("dp", "sp"))
    want = dense_attention_ref(q, k, v, causal=False)
    got = ring_attention(q, k, v, mesh, causal=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)


_CFG = TransformerConfig(vocab_size=128, dim=64, n_layers=2, n_heads=4,
                         hidden=128, max_seq=64, compute_dtype=jnp.float32)


def test_forward_ring_matches_local():
    params = jax.tree_util.tree_map(jnp.asarray, init_params(_CFG, seed=0))
    toks = jnp.asarray(np.random.RandomState(0).randint(
        128, size=(4, 32)).astype(np.int32))
    mesh = Mesh(np.asarray(jax.devices()).reshape(2, 2, 2),
                ("dp", "sp", "tp"))
    local = transformer_forward(params, toks, _CFG, mesh=None)
    ring = transformer_forward(params, toks, _CFG, mesh=mesh)
    np.testing.assert_allclose(np.asarray(ring), np.asarray(local),
                               atol=1e-3)


def test_trainer_loss_decreases_sharded():
    mesh = Mesh(np.asarray(jax.devices()).reshape(2, 2, 2),
                ("dp", "sp", "tp"))
    tr = TransformerTrainer(_CFG, mesh, updater_type="sgd")
    toks = np.random.RandomState(1).randint(
        128, size=(4, 32)).astype(np.int32)
    first = tr.train_step(toks)
    for _ in range(15):
        last = tr.train_step(toks)
    assert last < first * 0.7, (first, last)


def test_trainer_momentum_state():
    """Stateful updater threads through the pytree step."""
    mesh = Mesh(np.asarray(jax.devices()), ("dp",))
    tr = TransformerTrainer(_CFG, mesh, updater_type="momentum")
    toks = np.random.RandomState(2).randint(
        128, size=(2, 16)).astype(np.int32)
    tr.train_step(toks)
    v = tr.state["head"][0]
    assert float(jnp.abs(v).max()) > 0.0   # velocity populated


def test_bf16_compute_path():
    cfg = TransformerConfig(vocab_size=64, dim=32, n_layers=1, n_heads=2,
                            hidden=64, max_seq=32,
                            compute_dtype=jnp.bfloat16)
    params = jax.tree_util.tree_map(jnp.asarray, init_params(cfg, seed=0))
    toks = jnp.asarray(np.random.RandomState(0).randint(
        64, size=(2, 16)).astype(np.int32))
    out = transformer_forward(params, toks, cfg, mesh=None)
    assert out.dtype == jnp.bfloat16
    loss = lm_loss(params, toks, cfg)
    assert np.isfinite(float(loss))


@pytest.mark.parametrize("layout", ["contiguous", "zigzag"])
def test_ring_attention_layouts_exact(qkv, layout):
    q, k, v = qkv
    mesh = Mesh(np.asarray(jax.devices()).reshape(2, 4), ("dp", "sp"))
    want = dense_attention_ref(q, k, v)
    got = ring_attention(q, k, v, mesh, layout=layout)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)


def test_ring_zigzag_rejects_non_causal():
    mesh = Mesh(np.asarray(jax.devices()).reshape(2, 4), ("dp", "sp"))
    q = jnp.zeros((1, 1, 64, 16))
    with pytest.raises(ValueError, match="zigzag"):
        ring_attention(q, q, q, mesh, causal=False, layout="zigzag")


def _mesh2(names=("dp", "sp")):
    devs = np.array(jax.devices()[:2]).reshape(1, 2)
    return Mesh(devs, names)


@pytest.mark.parametrize("layout", ["contiguous", "zigzag"])
def test_ring_uses_flash_kernel_exact(monkeypatch, layout):
    """sp=2 ring with the Pallas kernel force-dispatched per ring step
    (interpret mode): the sp>1 path must hit kernel speed on TPU, so CI
    must prove the kernel path is numerically exact inside the ring."""
    monkeypatch.setenv("MVTPU_FORCE_FLASH", "interpret")
    rng = np.random.RandomState(11)
    q = jnp.asarray(rng.randn(1, 2, 256, 32).astype(np.float32)) * 0.4
    k = jnp.asarray(rng.randn(1, 2, 256, 32).astype(np.float32)) * 0.4
    v = jnp.asarray(rng.randn(1, 2, 256, 32).astype(np.float32)) * 0.4
    mesh = _mesh2()
    got = ring_attention(q, k, v, mesh, axis_name="sp", causal=True,
                         batch_axis="dp", head_axis=None, layout=layout)
    want = dense_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_scan_remat_matches_loop():
    """scan_layers + remat is a pure re-scheduling: forward logits and
    gradients must match the loop format bit-for-bit-ish (f32 tolerance)."""
    from dataclasses import replace

    from multiverso_tpu.models.transformer import stack_layer_params

    cfg_scan = replace(_CFG, scan_layers=True, remat=True)
    loop_params = jax.tree_util.tree_map(jnp.asarray,
                                         init_params(_CFG, seed=3))
    scan_params = dict(loop_params,
                       layers=stack_layer_params(loop_params["layers"]))
    toks = jnp.asarray(np.random.RandomState(3).randint(
        128, size=(2, 32)).astype(np.int32))

    out_loop = transformer_forward(loop_params, toks, _CFG, mesh=None)
    out_scan = transformer_forward(scan_params, toks, cfg_scan, mesh=None)
    np.testing.assert_allclose(np.asarray(out_scan), np.asarray(out_loop),
                               atol=1e-5)

    g_loop = jax.grad(lm_loss)(loop_params, toks, _CFG)
    g_scan = jax.grad(lm_loss)(scan_params, toks, cfg_scan)
    np.testing.assert_allclose(np.asarray(g_scan["head"]),
                               np.asarray(g_loop["head"]), atol=1e-5)
    g_scan_l0 = jax.tree_util.tree_map(lambda a: np.asarray(a[0]),
                                       g_scan["layers"])
    for key in ("wq", "w2", "attn_norm"):
        np.testing.assert_allclose(
            g_scan_l0[key], np.asarray(g_loop["layers"][0][key]), atol=1e-5)


def test_selective_remat_matches_full():
    """remat_policy='dots' (save matmul outputs, recompute attention) is a
    pure re-scheduling too: logits and grads must match full remat."""
    from dataclasses import replace

    from multiverso_tpu.models.transformer import stack_layer_params

    cfg_full = replace(_CFG, scan_layers=True, remat=True)
    cfg_sel = replace(cfg_full, remat_policy="dots")
    loop_params = jax.tree_util.tree_map(jnp.asarray,
                                         init_params(_CFG, seed=7))
    params = dict(loop_params,
                  layers=stack_layer_params(loop_params["layers"]))
    toks = jnp.asarray(np.random.RandomState(7).randint(
        128, size=(2, 32)).astype(np.int32))

    out_full = transformer_forward(params, toks, cfg_full, mesh=None)
    out_sel = transformer_forward(params, toks, cfg_sel, mesh=None)
    np.testing.assert_allclose(np.asarray(out_sel), np.asarray(out_full),
                               atol=1e-5)
    g_full = jax.grad(lm_loss)(params, toks, cfg_full)
    g_sel = jax.grad(lm_loss)(params, toks, cfg_sel)
    for key in ("wq", "w2", "attn_norm"):
        np.testing.assert_allclose(
            np.asarray(g_sel["layers"][key]),
            np.asarray(g_full["layers"][key]), atol=1e-5)

    with pytest.raises(ValueError, match="remat_policy"):
        transformer_forward(params, toks,
                            replace(cfg_full, remat_policy="bogus"),
                            mesh=None)


def test_scan_remat_trainer_sharded():
    """Full trainer on a (dp, sp, tp) mesh with scan+remat params: the
    stacked layout shards, trains, and the loss falls."""
    from dataclasses import replace

    cfg = replace(_CFG, scan_layers=True, remat=True)
    mesh = Mesh(np.asarray(jax.devices()).reshape(2, 2, 2),
                ("dp", "sp", "tp"))
    tr = TransformerTrainer(cfg, mesh, updater_type="sgd")
    assert isinstance(tr.params["layers"], dict)       # stacked format
    assert tr.params["layers"]["wq"].shape[0] == cfg.n_layers
    toks = np.random.RandomState(4).randint(
        128, size=(4, 32)).astype(np.int32)
    first = tr.train_step(toks)
    for _ in range(15):
        last = tr.train_step(toks)
    assert last < first * 0.7, (first, last)


def test_scan_remat_moe():
    """MoE layers stack and scan too."""
    from dataclasses import replace

    cfg = replace(_CFG, scan_layers=True, remat=True, num_experts=4,
                  top_k=2)
    params = jax.tree_util.tree_map(jnp.asarray, init_params(cfg, seed=5))
    assert params["layers"]["w1"].shape[:2] == (cfg.n_layers, 4)
    toks = jnp.asarray(np.random.RandomState(5).randint(
        128, size=(2, 16)).astype(np.int32))
    loss = lm_loss(params, toks, cfg)
    assert np.isfinite(float(loss))


def test_ring_flash_grad_matches_dense(monkeypatch):
    """Gradients through the ring with kernel pieces (the lse-cotangent
    path through the custom_vjp) match dense-attention gradients."""
    monkeypatch.setenv("MVTPU_FORCE_FLASH", "interpret")
    rng = np.random.RandomState(12)
    q = jnp.asarray(rng.randn(1, 2, 256, 32).astype(np.float32)) * 0.4
    k = jnp.asarray(rng.randn(1, 2, 256, 32).astype(np.float32)) * 0.4
    v = jnp.asarray(rng.randn(1, 2, 256, 32).astype(np.float32)) * 0.4
    mesh = _mesh2()

    def ring_loss(q, k, v):
        o = ring_attention(q, k, v, mesh, axis_name="sp", causal=True,
                           batch_axis="dp", head_axis=None)
        return jnp.sum(jnp.square(o))

    def dense_loss(q, k, v):
        return jnp.sum(jnp.square(dense_attention_ref(q, k, v, True)))

    got = jax.grad(ring_loss, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=4e-4)


def test_trainer_checkpoint_roundtrip_cross_mesh(mv, tmp_path):
    """TransformerTrainer.save/restore: exact state round trip, including
    restoring onto a DIFFERENT mesh layout (1-axis dp -> 3-axis
    dp/sp/tp), with stateful updater slots preserved."""
    from jax.sharding import PartitionSpec as P

    mv.init()
    toks = np.random.RandomState(6).randint(
        128, size=(4, 32)).astype(np.int32)

    mesh1 = Mesh(np.asarray(jax.devices()), ("dp",))
    tr = TransformerTrainer(_CFG, mesh1, updater_type="momentum")
    for _ in range(3):
        tr.train_step(toks)
    path = str(tmp_path / "trainer.ckpt")
    tr.save(path)
    want = jax.tree_util.tree_map(np.asarray, tr.params)
    tr.train_step(toks)                       # diverge past the snapshot
    tr.restore(path)
    got = jax.tree_util.tree_map(np.asarray, tr.params)
    jax.tree_util.tree_map(np.testing.assert_array_equal, got, want)

    mesh3 = Mesh(np.asarray(jax.devices()).reshape(2, 2, 2),
                 ("dp", "sp", "tp"))
    tr3 = TransformerTrainer(_CFG, mesh3, updater_type="momentum")
    tr3.restore(path)                         # cross-mesh re-placement
    got3 = jax.tree_util.tree_map(np.asarray, tr3.params)
    jax.tree_util.tree_map(np.testing.assert_array_equal, got3, want)
    assert tr3.params["head"].sharding.spec == P(None, "tp")
    # momentum slots restored too (non-zero after 3 steps)
    assert float(jnp.abs(tr3.state["head"][0]).max()) > 0
    # and training continues from the restored point
    loss = tr3.train_step(toks)
    assert np.isfinite(loss)



def test_ce_custom_vjp_matches_autodiff():
    """The CE custom_vjp (bf16 cotangent so the head backward runs MXU
    bf16 matmuls) must produce the same dlogits as plain autodiff of
    the f32 loss math — exactly in f32 mode (the cast is the identity,
    keeping the fp32 parity gates honest), and to bf16 rounding in bf16
    mode."""
    from multiverso_tpu.models.transformer import _ce, _ce_value

    rng = np.random.RandomState(0)
    for dt, tol in ((jnp.float32, 1e-6), (jnp.bfloat16, 2e-3)):
        logits = jnp.asarray(rng.randn(2, 8, 32), dt)
        tgt = jnp.asarray(rng.randint(32, size=(2, 8)), jnp.int32)
        g1 = jax.grad(lambda l: _ce(l, tgt))(logits)
        g2 = jax.grad(lambda l: _ce_value(l, tgt))(logits)
        assert g1.dtype == dt
        err = float(jnp.max(jnp.abs(g1.astype(jnp.float32)
                                    - g2.astype(jnp.float32))))
        assert err < tol, (dt, err)


def test_grad_accumulation_matches_full_batch(mv):
    """accum=2 (two microbatches, one update) must produce the same
    post-step params as the plain full-batch step in f32 — the CE is a
    mean over equal chunks, so summed-then-halved microbatch grads ARE
    the full-batch grads."""
    from jax.sharding import Mesh

    cfg = TransformerConfig(vocab_size=64, dim=32, n_layers=2, n_heads=2,
                            hidden=64, max_seq=16,
                            compute_dtype=jnp.float32)
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("dp",))
    toks = np.random.RandomState(3).randint(64, size=(8, 16)).astype(np.int32)

    tr_a = TransformerTrainer(cfg, mesh, updater_type="sgd")
    tr_b = TransformerTrainer(cfg, mesh, updater_type="sgd")
    loss_a = float(tr_a.train_step_async(toks))
    loss_b = float(tr_b.train_step_async(toks, accum=2))
    assert abs(loss_a - loss_b) < 1e-5, (loss_a, loss_b)
    for a, b in zip(jax.tree_util.tree_leaves(tr_a.params),
                    jax.tree_util.tree_leaves(tr_b.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-6)
    # bad split and dp-indivisible microbatch both fail loudly; MoE is
    # rejected (its aux loss is batch-nonlinear, accumulation would
    # silently change the objective)
    import pytest as _pytest
    with _pytest.raises(ValueError, match="divisible"):
        tr_b.train_step_async(toks[:6], accum=4)
    with _pytest.raises(ValueError, match="dp axis"):
        tr_b.train_step_async(toks, accum=8)   # microbatch 1 vs dp=2
    cfg_moe = TransformerConfig(vocab_size=64, dim=32, n_layers=2,
                                n_heads=2, hidden=64, max_seq=16,
                                num_experts=4, top_k=2)
    tr_moe = TransformerTrainer(cfg_moe, mesh, updater_type="sgd")
    with _pytest.raises(ValueError, match="MoE"):
        tr_moe.train_step_async(toks, accum=2)
