"""Mixture-of-Experts tests: routing exactness against a per-token
reference, load-balancing aux-loss behavior, transformer integration,
and an 8-device (dp, sp, tp, ep) expert-parallel training run."""

from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from multiverso_tpu.models.moe import init_moe_params, moe_ffn, moe_shardings
from multiverso_tpu.models import (TransformerConfig, TransformerTrainer,
                                   init_params)
from multiverso_tpu.models.transformer import lm_loss, transformer_forward


def _moe_reference(params, x, top_k):
    """Per-token loop over experts: the semantics moe_ffn must match."""
    B, T, dim = x.shape
    E = params["router"].shape[1]
    logits = x @ params["router"]
    probs = np.asarray(jax.nn.softmax(jnp.asarray(logits), axis=-1))
    out = np.zeros_like(x)
    for b in range(B):
        for t in range(T):
            idx = np.argsort(-probs[b, t])[:top_k]
            w = probs[b, t, idx]
            w = w / w.sum()
            for j, e in zip(range(top_k), idx):
                h = x[b, t] @ params["w1"][e]
                g = h / (1 + np.exp(-h))          # silu
                up = x[b, t] @ params["w3"][e]
                out[b, t] += w[j] * ((g * up) @ params["w2"][e])
    return out


@pytest.mark.parametrize("top_k", [1, 2])
def test_moe_matches_per_token_reference(top_k):
    rng = np.random.RandomState(0)
    params = init_moe_params(dim=16, hidden=32, num_experts=4, seed=1)
    x = rng.randn(2, 8, 16).astype(np.float32) * 0.5
    got, *_ = moe_ffn(params, jnp.asarray(x), top_k=top_k)
    want = _moe_reference(params, x, top_k)
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-4)


def test_moe_topk_equals_experts_is_full_softmax_mix():
    """top_k == E degenerates to a softmax-weighted mixture of all
    experts (no routing sparsity)."""
    rng = np.random.RandomState(1)
    E = 4
    params = init_moe_params(dim=16, hidden=32, num_experts=E, seed=2)
    x = jnp.asarray(rng.randn(1, 6, 16).astype(np.float32) * 0.5)
    got, *_ = moe_ffn(params, x, top_k=E)
    probs = jax.nn.softmax(x @ params["router"], axis=-1)
    gate = jax.nn.silu(jnp.einsum("btd,edh->beth", x, params["w1"]))
    up = jnp.einsum("btd,edh->beth", x, params["w3"])
    eo = jnp.einsum("beth,ehd->betd", gate * up, params["w2"])
    want = jnp.einsum("betd,bte->btd", eo, probs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_moe_aux_loss_balanced_vs_skewed():
    """Uniform routing gives aux ≈ top_k (its minimum); routing every
    token to one expert drives aux toward E."""
    rng = np.random.RandomState(2)
    E, k = 4, 1
    params = init_moe_params(dim=16, hidden=32, num_experts=E, seed=3)
    x = jnp.asarray(rng.randn(2, 32, 16).astype(np.float32))

    balanced = dict(params, router=jnp.zeros((16, E)))
    _, aux_bal, *_ = moe_ffn(balanced, x, top_k=k)
    assert abs(float(aux_bal) - k) < 0.05, float(aux_bal)

    skew = np.zeros((16, E), np.float32)
    skew[:, 0] = 100.0   # every token -> expert 0 (positive x => +logit)
    _, aux_skew, *_ = moe_ffn(dict(params, router=jnp.asarray(skew)),
                          jnp.abs(x), top_k=k)
    assert float(aux_skew) > 0.9 * E, float(aux_skew)


_MOE_CFG = TransformerConfig(vocab_size=64, dim=32, n_layers=2, n_heads=4,
                             hidden=64, max_seq=32, num_experts=4, top_k=2,
                             compute_dtype=jnp.float32)


def test_transformer_moe_forward_and_aux():
    params = jax.tree_util.tree_map(jnp.asarray,
                                    init_params(_MOE_CFG, seed=0))
    # the expert leaves sit at the layer's top level, expert-indexed
    assert params["layers"][0]["w1"].shape == (4, 32, 64)
    assert params["layers"][0]["router"].shape == (32, 4)
    toks = jnp.asarray(np.random.RandomState(0).randint(
        64, size=(2, 16)).astype(np.int32))
    logits, aux = transformer_forward(params, toks, _MOE_CFG,
                                      return_aux=True)
    assert logits.shape == (2, 16, 64)
    # aux is the weighted sum over layers; each layer's balancing term
    # >= top_k (its minimum)
    assert float(aux) >= (_MOE_CFG.aux_loss_coef * _MOE_CFG.n_layers
                          * _MOE_CFG.top_k * 0.99)
    loss_with_aux = lm_loss(params, toks, _MOE_CFG)
    assert np.isfinite(float(loss_with_aux))


@pytest.mark.parametrize("stack", ["layers", "scan_remat"])
def test_transformer_moe_trains_on_ep_mesh(stack):
    """Full 4-axis parallelism: dp x sp x tp x ep on the 8-device mesh,
    experts sharded over ep, loss decreases through the updater step;
    as a list of layers and scanned under remat (what
    ``__graft_entry__``'s ``ep`` arm runs)."""
    scan = stack == "scan_remat"
    cfg = replace(_MOE_CFG, scan_layers=scan, remat=scan)
    mesh = Mesh(np.asarray(jax.devices()).reshape(1, 2, 2, 2),
                ("dp", "sp", "tp", "ep"))
    shard = moe_shardings(mesh)
    assert shard["w1"].spec == jax.sharding.PartitionSpec("ep", None, None)
    tr = TransformerTrainer(cfg, mesh, updater_type="sgd")
    # expert weights really live sharded over ep (stacked: after the
    # layer axis)
    w1 = tr.params["layers"]["w1"] if scan else tr.params["layers"][0]["w1"]
    assert w1.sharding.spec[int(scan)] == "ep"
    toks = np.random.RandomState(3).randint(
        64, size=(2, 32)).astype(np.int32)
    first = tr.train_step(toks)
    for _ in range(10):
        last = tr.train_step(toks)
    assert last < first, (first, last)


def test_moe_grad_flows_to_all_routed_experts():
    params = init_moe_params(dim=16, hidden=32, num_experts=4, seed=4)
    x = jnp.asarray(np.random.RandomState(5).randn(2, 16, 16)
                    .astype(np.float32))

    def loss(p):
        out, aux, *_ = moe_ffn(p, x, top_k=2)
        return jnp.sum(jnp.square(out)) + 0.01 * aux

    g = jax.grad(loss)(params)
    # router always gets gradient (via combine weights + aux loss)
    assert float(jnp.abs(g["router"]).max()) > 0
    # with 32 tokens and top-2 of 4 experts, every expert is hit w.h.p.
    per_expert = jnp.max(jnp.abs(g["w2"]), axis=(1, 2))
    assert float(per_expert.min()) > 0


@pytest.mark.parametrize("dispatch", ["capacity", "nonesuch"])
def test_moe_refuses_a_schedule_it_does_not_have(dispatch):
    """``capacity`` (static buckets that dropped overflow routes) went in
    PR 28; it is refused like any other unknown name, with the two that
    exist."""
    params = init_moe_params(dim=16, hidden=32, num_experts=4, seed=0)
    x = jnp.zeros((1, 4, 16), jnp.float32)
    with pytest.raises(ValueError, match=r"grouped\|dense\)"):
        moe_ffn(params, x, dispatch=dispatch)
