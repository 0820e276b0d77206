"""Laguna-S-2.1 on the normal path (ISSUE 30): layers of different kinds
(window and full attention with their own head counts, grouped K/V heads, a
per-head gate, two rotary recipes, a leading dense layer), a share of the
experts beside a shared expert, held to the plain reference
``benchmarks/reference/laguna_lm.py``, small, on the CPU."""

import hashlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks import flops_laguna  # noqa: E402
from benchmarks.reference import laguna_lm  # noqa: E402
from multiverso_tpu import metrics  # noqa: E402
from multiverso_tpu.models import (TransformerConfig,  # noqa: E402
                                   TransformerTrainer, init_params)
from multiverso_tpu.models.moe import (_combine, _dispatch,  # noqa: E402
                                       init_moe_params, moe_ffn,
                                       shared_expert)
from multiverso_tpu.models.transformer import (Layout,  # noqa: E402
                                               _loss_and_routes, expert_load,
                                               lm_loss, param_shardings)
from multiverso_tpu.ops.flash_attention import flash_attention  # noqa: E402
from multiverso_tpu.parallel.ring_attention import (  # noqa: E402
    blockwise_attention_local, ring_attention)

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CONFIG = os.path.join(REPO, "benchmarks", "configs",
                      "laguna-s-2.1-l5-e16.json")
FULL, SLIDING = "full_attention", "sliding_attention"
PATTERN = [FULL, SLIDING, SLIDING, SLIDING]


def _model(n_layers: int = 5, **over) -> dict:
    """Laguna's block at toy widths: F S S S repeating from layer 0, layer 0
    dense, 4 query heads on full layers and 6 on sliding ones over 2 K/V
    heads, 2 of 8 experts held (the second share), top-3."""
    kinds = [PATTERN[i % 4] for i in range(n_layers)]
    model = dict(
        vocab_size=96, dim=32, n_layers=n_layers, n_heads=4, head_dim=8,
        n_kv_heads=2, hidden=16, dense_hidden=48, shared_expert_hidden=16,
        max_seq=64, norm_eps=1e-6, layer_types=kinds,
        heads_per_layer=[4 if k == FULL else 6 for k in kinds],
        mlp_layer_types=["dense"] + ["sparse"] * (n_layers - 1),
        layer_period=4, sliding_window=8,
        rope_full=dict(theta=5e5, rotary_factor=0.5, yarn_factor=8.0,
                       original_max_seq=16, beta_fast=32.0, beta_slow=1.0,
                       attention_factor=1.2),
        rope_sliding=dict(theta=1e4, rotary_factor=1.0),
        attn_gate="per_head", num_experts=8, experts_held=2, experts_first=2,
        top_k=3, norm_topk_prob=True, routed_scale=2.5,
        moe_dispatch="grouped", aux_loss_coef=0.0, router_z_loss_coef=0.0,
        scan_layers=True, remat=True, remat_policy="full")
    model.update(over)
    return model


def _tokens(vocab: int = 96, batch: int = 2, seq: int = 32, seed: int = 0):
    return jnp.asarray(np.random.RandomState(seed).randint(
        vocab, size=(batch, seq)).astype(np.int32))


def _rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


# ------------------------------------------------- system against reference
@pytest.mark.parametrize("n_layers,dispatch,scan", [
    (5, "grouped", True), (9, "grouped", True), (5, "dense", True),
    (5, "grouped", False)], ids=["1+4", "1+8", "1+4-dense", "1+4-loop"])
def test_system_matches_the_reference_in_float32(n_layers, dispatch, scan):
    """Loss and every leaf's gradient, every layer, in float32."""
    model = _model(n_layers, moe_dispatch=dispatch, scan_layers=scan)
    cfg = TransformerConfig(**model, compute_dtype=jnp.float32)
    lay = cfg.layout
    assert (len(lay.lead), len(lay.period), lay.n_periods, lay.n_trail) == (
        1, 4, (n_layers - 1) // 4, 0)
    params = jax.tree_util.tree_map(jnp.asarray, init_params(cfg, seed=1))
    tokens = _tokens()
    with jax.default_matmul_precision("highest"):
        (loss, routes), grads = jax.value_and_grad(
            _loss_and_routes, has_aux=True)(params, tokens, cfg, None)
    want_loss, want = laguna_lm.loss_and_grads(
        params, tokens, model, layers=tuple(range(n_layers)))
    assert abs(float(loss) - float(want_loss)) < 1e-5
    for i in range(n_layers):
        got = laguna_lm.layer(grads["layers"], i)
        assert set(got) == set(want["layers"][i])
        for key in got:
            assert _rel(got[key], want["layers"][i][key]) < 1e-4, (i, key)
    for key in ("embed", "out_norm"):
        assert _rel(grads[key], want[key]) < 1e-4, key
    # the step's counted routes: [routed layers, held + 1], all of them
    assert routes.shape == (n_layers - 1, 3)
    assert (np.asarray(routes).sum(axis=1) == tokens.size * 3).all()
    assert (np.asarray(expert_load(params, tokens, cfg))
            == np.asarray(routes)).all()


def test_bfloat16_system_stays_near_the_reference():
    """The cell's precision at toy widths, where a rounding moves more than
    at 3072 (``laguna_lm``'s docstring has the chip's numbers): the loss
    stays close, and no leaf is off by its own size."""
    model = _model(5)
    cfg = TransformerConfig(**model)
    params = jax.tree_util.tree_map(jnp.asarray, init_params(cfg, seed=2))
    tokens = _tokens(seed=3)
    (loss, _), grads = jax.value_and_grad(_loss_and_routes, has_aux=True)(
        params, tokens, cfg, None)
    want_loss, want = laguna_lm.loss_and_grads(params, tokens, model,
                                               layers=(0, 2, 4))
    assert abs(float(loss) - float(want_loss)) < 0.05
    for i in (0, 2, 4):
        got = laguna_lm.layer(grads["layers"], i)
        for key in ("wq", "wk", "wg", "w2", "attn_norm"):
            assert _rel(got[key], want["layers"][i][key]) < 0.5, (i, key)


def test_reference_refuses_a_full_causal_band_and_another_share():
    """What the comparison is for: the reference with the window taken away,
    or given another share of the experts, is far from the system."""
    model = _model(5)
    cfg = TransformerConfig(**model, compute_dtype=jnp.float32)
    params = jax.tree_util.tree_map(jnp.asarray, init_params(cfg, seed=1))
    tokens = _tokens()
    with jax.default_matmul_precision("highest"):
        loss = float(lm_loss(params, tokens, cfg))
    assert abs(float(laguna_lm.loss(params, tokens, model)) - loss) < 1e-5
    for wrong in (dict(sliding_window=10 ** 6), dict(experts_first=4),
                  dict(routed_scale=1.0), dict(attn_gate="")):
        off = abs(float(laguna_lm.loss(params, tokens, {**model, **wrong}))
                  - loss)
        assert off > 1e-3, (wrong, off)


def test_reference_routing_precision_option():
    """``routing_dtype`` runs the reference's router matmul and its input in
    the precision below: another function (routes swap), close to the first,
    and the option the chip runs use to show that the router's arithmetic is
    not what separates system and reference (``laguna_lm``'s docstring)."""
    model = _model(5)
    cfg = TransformerConfig(**model, compute_dtype=jnp.float32)
    params = jax.tree_util.tree_map(jnp.asarray, init_params(cfg, seed=1))
    tokens = _tokens()
    plain = float(laguna_lm.loss(params, tokens, model))
    rounded = float(laguna_lm.loss(params, tokens, model,
                                   routing_dtype=jnp.bfloat16))
    assert plain != rounded and abs(plain - rounded) < 0.05


# ------------------------------------------------------------- the kernels
def _dense_attention(q, k, v, window):
    B, H, T, D = q.shape
    group = H // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    s = jnp.einsum("bhtd,bhsd->bhts", q, k) * D ** -0.5
    t = jnp.arange(T)
    visible = t[None, :] <= t[:, None]
    if window is not None:
        visible = visible & (t[None, :] > t[:, None] - window)
    return jnp.einsum("bhts,bhsd->bhtd",
                      jax.nn.softmax(jnp.where(visible, s, -jnp.inf), -1), v)


@pytest.mark.parametrize("T,window,heads,kv,bq,bk", [
    (64, 128, 2, 2, 32, 32),       # T below the window: plain causal
    (128, 128, 4, 1, 64, 64),      # T equal to it
    (256, 128, 4, 2, 64, 64),      # above, window a multiple of the block
    (256, 100, 6, 2, 64, 128),     # a window that is no multiple of a block
    (256, 72, 4, 4, 128, 64),      # multi-head, q blocks wider than k blocks
    (256, None, 6, 2, 64, 64),     # grouped K/V heads without a window
], ids=["below", "equal", "above", "ragged", "mha", "gqa-causal"])
def test_flash_window_and_grouped_heads_against_dense_attention(
        T, window, heads, kv, bq, bk):
    """Forward and all three gradients in interpret mode; dk and dv come
    back at the K/V heads' shape, summed over each group's query heads."""
    rng = np.random.RandomState(T + heads)
    q = jnp.asarray(rng.randn(2, heads, T, 32), jnp.float32)
    k = jnp.asarray(rng.randn(2, kv, T, 32), jnp.float32)
    v = jnp.asarray(rng.randn(2, kv, T, 32), jnp.float32)
    w = jnp.asarray(rng.randn(2, heads, T, 32), jnp.float32)

    def flash(q, k, v):
        return jnp.sum(w * flash_attention(
            q, k, v, block_q=bq, block_k=bk, block_q_bwd=bq, block_k_bwd=bk,
            interpret=True, window=window, kv_heads=kv))

    def dense(q, k, v):
        return jnp.sum(w * _dense_attention(q, k, v, window))

    got = jax.value_and_grad(flash, (0, 1, 2))(q, k, v)
    want = jax.value_and_grad(dense, (0, 1, 2))(q, k, v)
    assert abs(float(got[0]) - float(want[0])) < 1e-3
    for g, r in zip(got[1], want[1]):
        assert g.shape == r.shape
        assert float(jnp.max(jnp.abs(g - r))) < 2e-5


def test_windowed_kernels_carry_their_own_names():
    q = jnp.ones((1, 4, 128, 32), jnp.float32)
    kv = jnp.ones((1, 2, 128, 32), jnp.float32)

    def names(window):
        text = str(jax.make_jaxpr(jax.grad(lambda q: jnp.sum(flash_attention(
            q, kv, kv, block_q=64, block_k=64, interpret=True,
            window=window))))(q))
        return {n for n in ("flash_fwd", "flash_bwd", "flash_bwd_dq",
                            "flash_bwd_dkv", "flash_win_fwd",
                            "flash_win_bwd", "flash_win_bwd_dq",
                            "flash_win_bwd_dkv") if f"name={n}\n" in text
                or f"name={n} " in text or f"{n}\n" in text}

    # the backward is one call a layer at these shapes (PR 35)
    assert names(64) == {"flash_win_fwd", "flash_win_bwd"}
    assert names(None) == {"flash_fwd", "flash_bwd"}


def test_flash_refuses_heads_that_do_not_group_and_a_window_without_causal():
    q = jnp.ones((1, 6, 64, 32))
    with pytest.raises(ValueError, match="K/V heads"):
        flash_attention(q, q[:, :4], q[:, :4], interpret=True)
    with pytest.raises(ValueError, match="kv_heads=3"):
        flash_attention(q, q[:, :2], q[:, :2], interpret=True, kv_heads=3)
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, q, q, causal=False, window=8, interpret=True)


def test_jnp_fallback_and_dispatch_carry_window_and_groups(monkeypatch):
    """Off the chip the streaming jnp body runs the same mask; with
    ``MVTPU_FORCE_FLASH`` the dispatcher hands the window to the kernel; a
    windowed trace is counted; a ring over ``sp`` refuses a window."""
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(1, 4, 128, 16), jnp.float32)
    k = jnp.asarray(rng.randn(1, 2, 128, 16), jnp.float32)
    v = jnp.asarray(rng.randn(1, 2, 128, 16), jnp.float32)
    want = _dense_attention(q, k, v, 40)
    counter = metrics.counter("attention.window_traced", {"window": "40"})
    before = counter.value
    got = blockwise_attention_local(q, k, v, 16 ** -0.5, window=40)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5
    monkeypatch.setenv("MVTPU_FORCE_FLASH", "1")
    got = blockwise_attention_local(q, k, v, 16 ** -0.5, window=40)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5
    assert counter.value == before + 2        # either body's trace counts
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("sp",))
    with pytest.raises(ValueError, match="sliding"):
        ring_attention(q, k, v, mesh, window=40)


# -------------------------------------------------- a share of the experts
def _moe_layer(seed: int = 0, E: int = 8, dim: int = 16, hidden: int = 8):
    full = init_moe_params(dim, hidden, E, seed=seed)
    rng = np.random.RandomState(seed + 1)
    for k, shape in (("shared_w1", (dim, hidden)), ("shared_w3", (dim, hidden)),
                     ("shared_w2", (hidden, dim))):
        full[k] = (rng.randn(*shape) * shape[0] ** -0.5).astype(np.float32)
    full["router"] = (rng.randn(dim, E)).astype(np.float32)
    full["mlp_norm"] = np.ones(dim, np.float32)
    return jax.tree_util.tree_map(jnp.asarray, full)


def _share(full, first, count):
    held = dict(full)
    for k in ("w1", "w3", "w2"):
        held[k] = full[k][first:first + count]
    return held


@pytest.mark.parametrize("dispatch", ["grouped", "dense"])
def test_the_shares_add_up_to_the_uncut_layer(dispatch):
    """8 experts in 4 shares of 2: the four shares' routed parts plus the
    shared expert once are the uncut reference layer, and their counted
    routes are its."""
    full = _moe_layer()
    x = jnp.asarray(np.random.RandomState(3).randn(2, 24, 16), jnp.float32)
    statics = dict(eps=1e-6, top_k=3, norm_topk_prob=True, routed_scale=2.5,
                   experts_first=0, routing_dtype=None)
    with jax.default_matmul_precision("highest"):
        # the reference layer holds every expert; its input is normed with a
        # gain of ones, so hand the shares the same normed rows
        want = laguna_lm._ffn(x, full, statics, "sparse") - x
        h = laguna_lm._rms_norm(x, full["mlp_norm"], 1e-6)
        total = shared_expert(full, h, jnp.float32)
        loads = []
        for first in (0, 2, 4, 6):
            out, _, _, load, _ = moe_ffn(
                _share(full, first, 2), h, top_k=3, dispatch=dispatch,
                norm_topk_prob=True, held=(first, 2), routed_scale=2.5,
                aux=False)
            total = total + out
            loads.append(np.asarray(load))
    assert _rel(total, want) < 1e-5
    loads = np.stack(loads)                       # [shares, 2 held + 1]
    assert (loads.sum(axis=1) == 2 * 24 * 3).all()
    _, _, _, whole, _ = moe_ffn(full, h, top_k=3, dispatch=dispatch,
                                aux=False)
    assert (loads[:, :2].reshape(-1) == np.asarray(whole)).all()


def test_a_share_is_the_uncut_layers_gradient_too():
    """Gradients of a share in ``grouped`` against the ``dense`` oracle given
    the same share, the router's through the renormalisation over all k."""
    full = _moe_layer(seed=4)
    held = _share(full, 2, 3)
    x = jnp.asarray(np.random.RandomState(5).randn(1, 32, 16), jnp.float32)
    w = jnp.asarray(np.random.RandomState(6).randn(1, 32, 16), jnp.float32)

    def out(params, x, dispatch):
        return jnp.sum(w * moe_ffn(params, x, top_k=3, dispatch=dispatch,
                                   held=(2, 3), routed_scale=2.5,
                                   aux=False)[0])

    with jax.default_matmul_precision("highest"):
        got = jax.grad(out, (0, 1))(held, x, "grouped")
        want = jax.grad(out, (0, 1))(held, x, "dense")
    for key in ("router", "w1", "w3", "w2"):
        assert _rel(got[0][key], want[0][key]) < 1e-5, key
    assert _rel(got[1], want[1]) < 1e-5


def test_routes_held_elsewhere_count_exactly_zero_whatever_their_rows_hold():
    """The grouped matmul leaves the rows beyond its groups to chance; a NaN
    there must not reach the output, the rows' cotangent or the weights'."""
    N, k, D = 6, 2, 4
    rng = np.random.RandomState(0)
    mine = jnp.asarray(rng.rand(N, k) < 0.5)
    key = jnp.where(mine, 0, 1).reshape(-1)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    inv = jnp.argsort(order).astype(jnp.int32).reshape(N, k)
    held_rows = int(mine.sum())
    clean = jnp.asarray(rng.randn(N * k, D), jnp.float32)
    clean = clean.at[held_rows:].set(0.0)
    dirty = clean.at[held_rows:].set(jnp.nan)
    top_p = jnp.asarray(rng.rand(N, k), jnp.float32)

    def combine(down):
        return jax.value_and_grad(
            lambda d, p: jnp.sum(_combine(d, p, order, inv, jnp.float32,
                                          mine) ** 2), (0, 1))(down, top_p)

    (out, (d_down, d_p)), (out0, (d_down0, d_p0)) = combine(dirty), combine(
        clean)
    assert np.isfinite(float(out)) and float(out) == float(out0)
    assert (np.asarray(d_down[held_rows:]) == 0).all()
    assert (np.asarray(d_down[:held_rows]) == np.asarray(
        d_down0[:held_rows])).all()
    assert np.isfinite(np.asarray(d_p)).all()
    assert (np.asarray(d_p)[~np.asarray(mine)] == 0).all()

    x = jnp.asarray(rng.randn(N, D), jnp.float32)
    _, pull = jax.vjp(lambda x: _dispatch(x, order, inv, mine), x)
    d_x, = pull(dirty)
    d_x0, = pull(clean)
    assert np.isfinite(np.asarray(d_x)).all()
    assert (np.asarray(d_x) == np.asarray(d_x0)).all()


def test_a_share_is_counted_and_a_wrong_one_refused():
    full = _moe_layer()
    x = jnp.ones((1, 8, 16))
    counter = metrics.counter("moe.held", {"held": "2", "of": "8"})
    before = counter.value
    moe_ffn(_share(full, 2, 2), x, top_k=3, held=(2, 2))
    assert counter.value == before + 1
    moe_ffn(full, x, top_k=3)                    # all held: not a share
    assert counter.value == before + 1
    with pytest.raises(ValueError, match="hold 2"):
        moe_ffn(_share(full, 2, 2), x, top_k=3, held=(2, 3))
    with pytest.raises(ValueError, match="router of 8"):
        moe_ffn(_share(full, 2, 2), x, top_k=3, held=(7, 2))


# ----------------------------------------------- one slot is today's program
# sha256 over every leaf (path, shape, bytes) of init_params(seed=11) and the
# float32 loss on RandomState(5) tokens, taken from the tree before this
# change (commit 547a884) on this machine's CPU backend; taken again, once,
# from PR 46's tree, which draws the weights on the device from a seeded key
# (the lowered train step's text stayed equal, parent against change, in
# every cell: ``tools/step_hashes.py``; old and new values in CHANGES.md).
BEFORE = {
    "dense": ("4755f099ae40e25a78a6b0edab9f0899b8a665fb2d0c7481543034cf654bd1c6",
              "0x1.41a5a80000000p+2",
              dict(vocab_size=96, dim=32, n_layers=3, n_heads=2, hidden=48,
                   max_seq=32, scan_layers=True, remat=True,
                   remat_policy="dots")),
    "olmoe": ("8886693fce0aaacad98ca08de82707c0fb42c207456f59c7661218b21f2bc2d3",
              "0x1.41af920000000p+2",
              dict(vocab_size=96, dim=32, n_layers=2, n_heads=2, hidden=24,
                   max_seq=32, num_experts=8, top_k=3, qk_norm=True,
                   norm_topk_prob=False, router_z_loss_coef=0.001,
                   aux_loss_coef=0.01, moe_dispatch="grouped",
                   scan_layers=True, remat=True, remat_policy="full")),
}


@pytest.mark.parametrize("name", sorted(BEFORE))
def test_one_slot_is_the_parameter_tree_and_loss_it_was(name):
    tree, loss, model = BEFORE[name]
    cfg = TransformerConfig(**model)
    assert cfg.layout == Layout((), cfg.layout.period, model["n_layers"], 0)
    assert cfg.layout.uniform and not cfg.counts_routes
    host = init_params(cfg, seed=11)
    assert isinstance(host["layers"], dict) and "period" not in host["layers"]
    digest = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(host)[0]:
        digest.update(jax.tree_util.keystr(path).encode())
        digest.update(str(leaf.shape).encode())
        digest.update(np.ascontiguousarray(leaf).tobytes())
    assert digest.hexdigest() == tree
    tokens = jnp.asarray(np.random.RandomState(5).randint(
        96, size=(2, 32)).astype(np.int32))
    got = jax.jit(lambda p, t: lm_loss(p, t, cfg))(
        jax.tree_util.tree_map(jnp.asarray, host), tokens)
    assert float(got).hex() == loss


# ------------------------------------------------------- the published depth
def test_the_48_layer_pattern_builds_steps_and_compiles_once():
    model = _model(48, dim=16, head_dim=4, hidden=8, dense_hidden=16,
                   shared_expert_hidden=8, max_seq=32, layer_period=0)
    cfg = TransformerConfig(**model)
    lay = cfg.layout                     # found without being told the period
    assert (len(lay.lead), len(lay.period), lay.n_periods, lay.n_trail) == (
        1, 4, 11, 3)
    assert [k.attn for k in lay.period] == [SLIDING, SLIDING, SLIDING, FULL]
    assert lay.kinds[0].ffn == "dense" and lay.kinds[47].attn == SLIDING
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("dp",))
    trainer = TransformerTrainer(cfg, mesh, seed=0)
    layers = trainer.params["layers"]
    assert (len(layers["lead"]), len(layers["period"]),
            len(layers["trail"])) == (1, 4, 3)
    assert layers["period"][3]["wq"].shape == (11, 16, 4 * 4)
    assert layers["period"][0]["wq"].shape == (11, 16, 6 * 4)
    assert layers["period"][0]["w1"].shape == (11, 2, 16, 8)
    tokens = np.asarray(_tokens(seq=32))
    losses = [float(trainer.train_step_async(tokens)) for _ in range(2)]
    step, _ = trainer._jitted_step()
    # (a trainer's second step is a second entry, its inputs being the
    # first's outputs: so it is for every configuration)
    compiled = step._cache_size()
    losses += [float(trainer.train_step_async(tokens)) for _ in range(2)]
    assert step._cache_size() == compiled <= 2
    assert all(np.isfinite(losses)) and losses[3] < losses[0]
    assert trainer.routes.shape == (47, 3)
    # depth costs the trace nothing: the 44 layers of the 11 periods are one
    # scan whose body holds four layers
    jaxpr = str(jax.make_jaxpr(lambda p, t: lm_loss(p, t, cfg))(
        trainer.params, jnp.asarray(tokens)))
    assert jaxpr.count("length=11") >= 1


def test_shardings_follow_the_grouped_tree_and_refusals_name_the_cause():
    model = _model(9)
    cfg = TransformerConfig(**model)
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("tp",))
    host = init_params(cfg, seed=0)
    shardings = param_shardings(cfg, mesh)
    assert (jax.tree_util.tree_structure(
        jax.tree_util.tree_map(lambda a: 0, host))
        == jax.tree_util.tree_structure(
            jax.tree_util.tree_map(lambda s: 0, shardings)))
    slot = shardings["layers"]["period"][0]
    assert slot["wg"].spec == (None, None, "tp")
    assert slot["shared_w2"].spec == (None, "tp", None)
    assert shardings["layers"]["lead"][0]["wk"].spec == (None, "tp")
    with pytest.raises(ValueError, match="K/V heads"):
        param_shardings(cfg, Mesh(np.asarray(jax.devices()[:4]), ("tp",)))
    params = jax.tree_util.tree_map(jnp.asarray, host)
    pp = Mesh(np.asarray(jax.devices()[:2]), ("pp",))
    with pytest.raises(ValueError, match="pipeline_microbatches"):
        lm_loss(params, _tokens(), TransformerConfig(
            **{**model, "pipeline_microbatches": 2}), pp)
    sp = Mesh(np.asarray(jax.devices()[:2]), ("sp",))
    with pytest.raises(ValueError, match="sliding_attention"):
        lm_loss(params, _tokens(), cfg, sp)
    with pytest.raises(ValueError, match="layer_types lists"):
        TransformerConfig(**{**model, "layer_types": [FULL]})


def test_layers_differ_under_their_own_scopes():
    model = _model(5)
    cfg = TransformerConfig(**model, compute_dtype=jnp.float32)
    params = jax.tree_util.tree_map(jnp.asarray, init_params(cfg, seed=0))
    text = jax.jit(lambda p, t: lm_loss(p, t, cfg)).lower(
        params, _tokens()).as_text(debug_info=True)
    for scope in ("attn/attn.full", "attn/attn.sliding", "mlp/moe.shared",
                  "mlp/moe.dispatch"):
        assert scope in text, scope
    uniform = TransformerConfig(vocab_size=96, dim=32, n_layers=2, n_heads=2,
                                hidden=48, max_seq=32)
    text = jax.jit(lambda p, t: lm_loss(p, t, uniform)).lower(
        jax.tree_util.tree_map(jnp.asarray, init_params(uniform)),
        _tokens()).as_text(debug_info=True)
    assert "attn.full" not in text and "attn/" in text


# ---------------------------------------------------- the benchmark's counts
def _configuration() -> dict:
    with open(CONFIG) as f:
        return json.load(f)


def test_required_work_by_hand():
    model = _configuration()["model"]
    d, hd = 3072, 128
    full = 2 * d * 48 * hd + 2 * d * 8 * hd + d * 48           # 44.2M
    sliding = 2 * d * 72 * hd + 2 * d * 8 * hd + d * 72        # 63.1M
    assert flops_laguna.attention_matmul_params(model, 48) == full
    assert flops_laguna.attention_matmul_params(model, 72) == sliding
    every_token = (2 * full + 3 * sliding + 3 * d * 12288
                   + 4 * (d * 256 + 3 * d * 1024) + 12544 * d)
    assert flops_laguna.token_matmul_params(model) == every_token
    assert round(every_token / 1e6, 1) == 470.5
    T, W = 8192, 512
    assert flops_laguna.attention_pairs(T) == T * (T + 1) // 2
    assert flops_laguna.attention_pairs(T, W) == (
        W * (W + 1) // 2 + (T - W) * W) == 4063488
    assert flops_laguna.attention_pairs(256, W) == 256 * 257 // 2
    assert flops_laguna.attention_flops(model, 1, T, SLIDING) == (
        3 * 4.0 * 3 * 72 * 4063488 * hd)
    assert flops_laguna.attention_flops(model, 1, T, FULL, backward=False
                                        ) == 4.0 * 2 * 48 * (T * (T + 1) // 2) * hd
    routes = 20480.0          # 6.25% of 4 layers x 81,920
    assert flops_laguna.routed_flops(model, routes) == (
        9 * 2 * routes * d * 1024)
    assert flops_laguna.train_flops(model, 1, T, routes) == (
        6.0 * every_token * T + 9 * 2 * routes * d * 1024
        + flops_laguna.attention_flops(model, 1, T, FULL)
        + flops_laguna.attention_flops(model, 1, T, SLIDING))
    assert flops_laguna.grouped_matmul_bytes(model, routes) == (
        9.0 * (4 * 16 * d * 1024 + routes * (d + 1024)) * 2)
    tensor = T * hd * 2
    b = flops_laguna.flash_kernel_bytes(model, 1, T, SLIDING)
    assert b["fwd"] == 3 * ((2 * 72 + 2 * 8) * tensor + 72 * T * 4)
    assert b["dkv"] == 3 * ((2 * 72 + 4 * 8) * tensor + 2 * 72 * T * 4)
    # a uniform dense model counts as benchmarks/flops.py counts it
    from benchmarks import flops
    dense = dict(vocab_size=512, dim=128, n_layers=2, n_heads=2, hidden=256)
    assert flops_laguna.token_matmul_params(dense) == flops.matmul_params(
        dense)


def test_trace_reduction_books_the_kinds_scopes_and_windowed_kernels():
    """``benchmarks/trace/kinds.py`` on hand-made device events."""
    from benchmarks.harness import Reading
    from benchmarks.trace import kinds
    from benchmarks.trace.program import ScopeIndex
    from benchmarks.trace.reduce import DeviceLines, Event, Trace

    us = 1000.0
    call = ('%{} = bf16[8,8]{{1,0}} custom-call(%p), '
            'custom_call_target="tpu_custom_call"')
    fusion = "%fusion.{} = f32[8]{{0}} fusion(%p), kind=kLoop"
    ops = [Event(fusion.format(1), 0, 10 * us),
           Event(call.format("flash_win_fwd.3"), 10 * us, 30 * us),
           Event(call.format("flash_win_bwd_dkv.4"), 30 * us, 45 * us),
           Event(call.format("flash_fwd.5"), 45 * us, 50 * us),
           Event(fusion.format(6), 50 * us, 58 * us),
           Event(call.format("ragged-dot-none.7"), 58 * us, 70 * us),
           Event(fusion.format(8), 70 * us, 90 * us)]
    body = "jit(step)/jvp(layers)/while/body/"
    index = ScopeIndex()
    index.op_names.update({
        "fusion.1": body + "attn/attn.sliding/dot_general",
        "flash_win_fwd.3": body + "attn/attn.sliding/flash_win_fwd/"
                                  "flash_win_fwd/pallas_call",
        "flash_win_bwd_dkv.4": "jit(step)/transpose(jvp(layers))/while/body/"
                               "attn/attn.sliding/flash_win_bwd_dkv/"
                               "flash_win_bwd_dkv/pallas_call",
        "flash_fwd.5": body + "attn/attn.full/flash_fwd/flash_fwd/"
                              "pallas_call",
        "fusion.6": body + "mlp/moe.shared/dot_general",
        "ragged-dot-none.7": "ragged-dot-none",
        "fusion.8": body + "mlp/dot_general"})
    trace = Trace(
        devices={"/device:TPU:0": DeviceLines(
            ops=ops, modules=[Event("jit_step(1)", 0, 45 * us),
                              Event("jit_step(1)", 45 * us, 90 * us)])},
        host=[Event("bench.window", 0, 100 * us)])
    got = kinds.summarize(trace, index)
    assert got.step_programs == 2 and got.busy_s == pytest.approx(90e-6)
    assert got.by_scope_s == pytest.approx({
        "attn.sliding": 45e-6, "attn.full": 5e-6, "moe.shared": 8e-6})
    assert got.by_kernel_s == pytest.approx({
        "flash_win_fwd": 20e-6, "flash_win_bwd_dq": 0.0,
        "flash_win_bwd_dkv": 15e-6})
    assert got.grouped_matmul_s == pytest.approx(12e-6)
    uniform = Trace(devices={"/device:TPU:0": DeviceLines(
        ops=ops[3:4] + ops[6:], modules=[])}, host=trace.host)
    index.op_names["flash_fwd.5"] = body + "attn/flash_fwd/flash_fwd/x"
    assert kinds.summarize(uniform, index) is None
    # the counter's reader needs no trace; the others give nothing without
    reading = Reading(facts={"held_routes_per_step": 20480.0,
                             "routes_per_step": 327680}, trace=None,
                      peaks={}, compiles_in_window=0)
    assert kinds.held_route_share(reading) == pytest.approx(6.25)
    assert kinds.kernel_roofline(reading, "flash_win_fwd") is None
    assert kinds.gmm_held_roofline(reading) is None
    assert kinds.held_route_share(Reading({}, None, {}, 0)) is None


def test_benchmark_lists_the_cell_where_its_readers_are_right():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = "laguna-s-2.1-l5-e16.zipf-seq8k-b1"
    row, = [w for w in bench["workloads"] if w["name"] == cell]
    assert (row["config"], row["traffic"], row["chips"]) == (
        "laguna-s-2.1-l5-e16", "zipf-seq8k-b1", 1)
    listed = {m["name"] for m in bench["per_layer"] + bench["end_to_end"]
              if cell in m.get("workloads", ())}
    assert {"tokens_per_chip_s", "kernel.flash_fwd_roofline",
            "kernel.flash_win_fwd_roofline", "kernel.flash_win_bwd_roofline",
            "kernel.flash_bwd_roofline", "kernel.moe_gmm_held_roofline",
            "model.attn_sliding_ms_per_step", "model.attn_full_ms_per_step",
            "model.moe_held_route_share", "model.moe_share"} <= listed
    # the split calls' entries were retired with the calls (PR 39)
    assert not listed & {"kernel.moe_gmm_roofline", "kernel.flash_share",
                         "kernel.flash_roofline",
                         "kernel.flash_win_dq_roofline",
                         "kernel.flash_win_dkv_roofline"}
    assert bench["workloads"][5] is row          # later PRs' cells follow
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    from benchmarks import harness
    readers = harness.layer_readers((os.path.join(REPO, "benchmarks"),))
    loaded = harness.load_cell(cell)
    assert {m["name"] for m in loaded.per_layer} <= set(readers)
    assert loaded.traffic["batch"] * loaded.traffic["seq"] == 8192


# ------------------------------------------------------- the configuration
def test_configuration_file_agrees_with_itself():
    from benchmarks import harness
    runner = harness.load_module((os.path.join(REPO, "benchmarks"),),
                                 "runners", "lm_train_kinds")
    config = _configuration()
    runner._check_published(config)
    model = config["model"]
    cfg = TransformerConfig(**model)                 # every key is a field
    lay = cfg.layout
    assert (len(lay.lead), len(lay.period), lay.n_periods, lay.n_trail) == (
        1, 4, 1, 0)
    assert cfg.counts_routes and cfg.held == (0, 16)
    assert cfg.vocab_size >= 12288     # lm_loss takes the _ce custom vjp
    assert sorted(config["reduced"]) == sorted([
        "num_hidden_layers", "layer_types", "mlp_layer_types", "gating_types",
        "num_attention_heads_per_layer", "num_experts", "vocab_size",
        "max_position_embeddings"])
    for wrong in (dict(num_key_value_heads=4), dict(sliding_window=256),
                  dict(moe_routed_scaling_factor=1.0),
                  dict(num_attention_heads_per_layer=[48] * 5)):
        with pytest.raises(ValueError, match="model group runs"):
            runner._check_published({**config, **wrong})
    # the reference's inverse frequencies are the program's
    from multiverso_tpu.models.common import Rope, rope_freqs
    for key in ("rope_full", "rope_sliding"):
        rotated = int(128 * model[key]["rotary_factor"])
        want = laguna_lm.inverse_frequencies(model[key], rotated)
        got = np.asarray(rope_freqs(Rope(**model[key]), rotated // 2))
        assert np.allclose(got, want, rtol=1e-6, atol=0)
    # YaRN moved the slow dims and left the fast ones: by hand, dim 64
    yarn = laguna_lm.inverse_frequencies(model["rope_full"], 64)
    plain = 500000.0 ** (-np.arange(32) / 32)
    assert np.allclose(yarn[:8], plain[:8]) and np.allclose(
        yarn[-4:], plain[-4:] / 128)


def test_configuration_file_holds_the_catalog_row():
    """The catalog is the machine's, not the repository's: it may be absent
    or hold no row for this source, and then there is nothing to compare."""
    config = _configuration()
    if not os.path.isfile(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        rows = {r["source_url"]: r for r in map(json.loads, f)}
    if config["source"] not in rows:
        pytest.skip(f"the catalog here ({len(rows)} rows) has no row for "
                    f"{config['source']}")
    published = rows[config["source"]]["config"]
    for key, value in published.items():
        if key not in config["reduced"]:
            assert config[key] == value, key
    assert config["published"]["num_experts"] == published["num_experts"]
    assert config["published"]["vocab_size"] == published["vocab_size"]
    for key in ("layer_types", "mlp_layer_types", "gating_types",
                "num_attention_heads_per_layer"):
        assert config[key] == published[key][:config["num_hidden_layers"]]


# ------------------------------------------------------ the cell, rehearsed
def test_the_cell_rehearses_at_toy_widths(tmp_path, monkeypatch):
    """The real runner, generator, reference and readers on the cell's own
    files shrunk to toy widths, on the CPU with the kernels interpreted:
    every check but the reference's tolerance holds as on the chip (at width
    128 a bfloat16 rounding moves a gradient by a fifth; the bounds there are
    the toy's), and the counter's metric is read."""
    import time

    from benchmarks import harness

    monkeypatch.setenv("MVTPU_FORCE_FLASH", "1")
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["paths"] = ["tinybench"]
    config = _configuration()
    config.update(hidden_size=128, head_dim=32, num_attention_heads=4,
                  num_key_value_heads=2, intermediate_size=256,
                  moe_intermediate_size=64,
                  shared_expert_intermediate_size=64, num_experts=4,
                  num_experts_per_tok=3, sliding_window=64, vocab_size=512,
                  max_position_embeddings=512,
                  num_attention_heads_per_layer=[4, 6, 6, 6, 4])
    config["published"]["router_width"] = 16
    config["rope_parameters"]["full_attention"][
        "original_max_position_embeddings"] = 512
    config["model"].update(
        dim=128, head_dim=32, n_heads=4, n_kv_heads=2, dense_hidden=256,
        hidden=64, shared_expert_hidden=64, num_experts=16, experts_held=4,
        top_k=3, sliding_window=64, vocab_size=512, max_seq=512,
        heads_per_layer=[4, 6, 6, 6, 4])
    config["model"]["rope_full"]["original_max_seq"] = 512
    config["trainer"]["learning_rate"] = 0.02
    for declared in bench["configs"]:
        if declared["name"] == config["name"]:
            declared["file"] = "tinybench/configs/laguna.json"
    with open(os.path.join(REPO, "benchmarks", "traffic",
                           "zipf-seq8k-b1.json")) as f:
        traffic = json.load(f)
    traffic.update(batch=1, seq=512, check={"batch": 1, "seq": 256},
                   trace_seconds=0.5)
    for path, obj in (("tinybench/configs/laguna.json", config),
                      ("tinybench/traffic/zipf-seq8k-b1.json", traffic),
                      ("BENCHMARK.json", bench)):
        os.makedirs(os.path.dirname(tmp_path / path), exist_ok=True)
        with open(tmp_path / path, "w") as f:
            json.dump(obj, f)
    cell = harness.load_cell("laguna-s-2.1-l5-e16.zipf-seq8k-b1",
                             root=str(tmp_path))
    reference = harness.load_module(cell.search, "reference", "laguna_lm")
    monkeypatch.setattr(reference, "GRAD_RTOL", 0.6)
    monkeypatch.setattr(reference, "GRAD_RTOL_ROUTED", 0.9)
    monkeypatch.setattr(reference, "LOSS_ATOL", 0.05)
    logged = []
    monkeypatch.setattr(harness.Runtime, "log",
                        lambda self, **fields: logged.append(fields))
    result = harness.run_cell(cell, seed=3000000007, seconds=0.5, trace=True,
                              t_start=time.perf_counter(), rehearsal=True,
                              out_root=str(tmp_path))
    assert result["correct"], [f for f in logged if "failed_checks" in f]
    assert result["failed"] == 0 and result["attempted"] >= 2
    share = result["metrics"]["model.moe_held_route_share"]["value"]
    assert 5.0 < share < 60.0                 # 25 under even routing
    check, = [f["reference_check"] for f in logged if "reference_check" in f]
    assert len(check["grad_rel_err"]) == 24
    runner = harness.load_module(cell.search, "runners", "lm_train_kinds")
    assert sorted(k for k in check["grad_rel_err"] if runner.routed(k)) == [
        "L2.router", "L2.w2", "L4.router", "L4.w2"]
    assert check["worst_routed"] == max(
        v for k, v in check["grad_rel_err"].items() if runner.routed(k))
    held, = [f["held_routes"] for f in logged if "held_routes" in f]
    assert held["of"] == 4 * 512 * 3 and len(held["per_layer"]) == 4
    traced, = [f["attention_traced"] for f in logged
               if "attention_traced" in f]
    assert traced["jnp"] == 0 and traced["window"] >= 1
