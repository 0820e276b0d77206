"""SmallThinker-21BA3B-Instruct on the normal path (ISSUE 48): a router that
reads the attention sub-layer's own input and chooses before attention runs,
ReLU-gated experts all held, full layers without a position embedding among
windowed, rotated ones over grouped K/V heads (7 query heads a K/V head), held
to the plain reference ``benchmarks/reference/smallthinker_lm.py``, small, on
the CPU."""

import importlib
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks import flops_smallthinker as counts  # noqa: E402
from benchmarks.reference import smallthinker_lm  # noqa: E402
from multiverso_tpu import metrics  # noqa: E402
from multiverso_tpu.models import (TransformerConfig,  # noqa: E402
                                   TransformerTrainer, init_params)
from multiverso_tpu.models.moe import (_routing, init_moe_params,  # noqa: E402
                                       moe_ffn, moe_route)
from multiverso_tpu.models.transformer import lm_loss  # noqa: E402
from multiverso_tpu.updaters import AddOption  # noqa: E402

# the module: ``multiverso_tpu.ops`` exports the function under its name
fa = importlib.import_module("multiverso_tpu.ops.flash_attention")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CONFIG = os.path.join(REPO, "benchmarks", "configs",
                      "smallthinker-21b-a3b-l4-e64.json")
CELL = "smallthinker-21b-a3b-l4-e64.zipf-seq16k-b1-chk8k"
NOPE, SLIDING = "full_attention_nope", "sliding_attention"
PERIOD = [NOPE, SLIDING, SLIDING, SLIDING]


def _model(periods: int = 1, **over) -> dict:
    """The block at toy widths: F S S S, 14 query heads over 2 K/V heads (7 a
    head), 8 ReLU-gated experts all held, top-3, the router reading the
    attention's input."""
    model = dict(
        vocab_size=96, dim=32, n_layers=4 * periods, n_heads=14, head_dim=8,
        n_kv_heads=2, hidden=16, max_seq=64, norm_eps=1e-6,
        layer_types=PERIOD * periods, layer_period=4, sliding_window=16,
        rope_sliding=dict(theta=1.5e6, rotary_factor=1.0), num_experts=8,
        top_k=3, norm_topk_prob=True, moe_dispatch="grouped",
        aux_loss_coef=0.0, router_z_loss_coef=0.0, router_input="attn",
        ffn_act="relu", scan_layers=True, remat=True, remat_policy="full")
    model.update(over)
    return model


def _tokens(vocab: int = 96, batch: int = 2, seq: int = 64, seed: int = 0):
    return jnp.asarray(np.random.RandomState(seed).randint(
        vocab, size=(batch, seq)).astype(np.int32))


def _rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def _loss_and_grads(model, params, tokens, dtype=jnp.float32):
    cfg = TransformerConfig(**model, compute_dtype=dtype)
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(lambda p: lm_loss(p, tokens, cfg))(params)


def _worst(model, grads, want, layers):
    worst = _rel(grads["embed"], want["embed"])
    for i in layers:
        mine = smallthinker_lm.layer(grads["layers"], i)
        worst = max([worst] + [_rel(mine[k], want["layers"][i][k])
                               for k in mine])
    return max(worst, _rel(grads["out_norm"], want["out_norm"]))


# ------------------------------------------------- system against reference
@pytest.mark.parametrize("periods,dispatch,scan", [
    (1, "grouped", True), (2, "grouped", True), (1, "dense", True),
    (1, "grouped", False), (2, "dense", False)])
def test_the_model_matches_the_reference_in_float32(periods, dispatch, scan):
    model = _model(periods, moe_dispatch=dispatch, scan_layers=scan)
    params = init_params(TransformerConfig(**model), seed=3)
    tokens = _tokens()
    layers = tuple(range(4 * periods))
    loss, grads = _loss_and_grads(model, params, tokens)
    want_loss, want = smallthinker_lm.loss_and_grads(params, tokens, model,
                                                     layers=layers)
    assert abs(float(loss) - float(want_loss)) < 2e-5
    assert _worst(model, grads, want, layers) < 2e-4


def test_bfloat16_lies_near_the_reference():
    model = _model()
    params = init_params(TransformerConfig(**model), seed=5)
    tokens = _tokens(seed=1)
    loss, grads = _loss_and_grads(model, params, tokens, jnp.bfloat16)
    want_loss, want = smallthinker_lm.loss_and_grads(params, tokens, model,
                                                     layers=(0, 1, 2, 3))
    assert abs(float(loss) - float(want_loss)) < 0.05
    # embedding rows and norm gains: a few routes flip at width 32
    assert _rel(grads["embed"], want["embed"]) < 0.5
    assert _rel(grads["out_norm"], want["out_norm"]) < 0.2


@pytest.mark.parametrize("control,moves", [
    ({"router_input": "mlp"}, True), ({"act": "silu"}, True),
    ({"rope_full": True}, True), ({"window_off": True}, True),
    ({}, False)])
def test_another_mathematics_fails_the_comparison(control, moves):
    """The route is the attention input's: a reference whose router reads the
    FFN's input is another model, as are SiLU experts, a rotated full layer
    and a band switched off; the program agrees with none of them."""
    model = _model()
    params = init_params(TransformerConfig(**model), seed=3)
    tokens = _tokens()
    loss, grads = _loss_and_grads(model, params, tokens)
    want_loss, want = smallthinker_lm.loss_and_grads(
        params, tokens, model, layers=(0, 1, 2, 3), control=control)
    apart = _worst(model, grads, want, (0, 1, 2, 3))
    assert (apart > 0.02) is moves, apart


def test_a_block_whose_router_reads_the_ffn_input_is_another_program():
    model = _model()
    params = init_params(TransformerConfig(**model), seed=3)
    tokens = _tokens()
    early, _ = _loss_and_grads(model, params, tokens)
    late, late_grads = _loss_and_grads(dict(model, router_input="mlp"),
                                       params, tokens)
    assert abs(float(early) - float(late)) > 1e-3
    # ... and that one is the reference's control, to float32 rounding
    want_loss, want = smallthinker_lm.loss_and_grads(
        params, tokens, model, layers=(0, 1, 2, 3),
        control={"router_input": "mlp"})
    assert abs(float(late) - float(want_loss)) < 2e-5
    assert _worst(model, late_grads, want, (0, 1, 2, 3)) < 2e-4


# ----------------------------------------------------------------- the route
def test_softmax_over_the_chosen_is_softmax_topk_renormalised():
    """``moe_primary_router_apply_softmax`` + ``norm_topk_prob``: the 6
    largest logits, softmax over those 6, is what ``_routing(scoring=
    "softmax", norm_topk_prob=True)`` computes, to float32 rounding."""
    params = init_moe_params(32, 16, 64, seed=2)
    h = jax.random.normal(jax.random.key(1), (2, 24, 32), jnp.float32) * 3.0
    _, logits, top_p, top_idx, _ = _routing(params, h, 6, True)
    top_r, top_e = jax.lax.top_k(logits, 6)
    np.testing.assert_array_equal(np.asarray(top_idx), np.asarray(top_e))
    np.testing.assert_allclose(np.asarray(top_p),
                               np.asarray(jax.nn.softmax(top_r, axis=-1)),
                               rtol=2e-6, atol=1e-7)


@pytest.mark.parametrize("dispatch", ["grouped", "dense"])
def test_a_route_handed_in_is_the_route_made_inside(dispatch):
    params = init_moe_params(32, 16, 8, seed=4)
    x = jax.random.normal(jax.random.key(0), (2, 16, 32), jnp.float32)
    kw = dict(top_k=3, dispatch=dispatch, aux=False, act="relu")
    inside = moe_ffn(params, x, **kw)
    handed = moe_ffn(params, x, route=moe_route(params, x, 3), **kw)
    np.testing.assert_array_equal(np.asarray(inside[0]),
                                  np.asarray(handed[0]))
    np.testing.assert_array_equal(np.asarray(inside[3]),
                                  np.asarray(handed[3]))
    # a route from other rows sends the same rows to other experts
    other = moe_ffn(params, x, route=moe_route(params, x[:, ::-1], 3), **kw)
    assert _rel(other[0], inside[0]) > 0.1
    with pytest.raises(ValueError, match="unknown gate activation"):
        moe_ffn(params, x, act="gelu")


def test_the_router_learns_through_the_attention_input():
    """The router's gradient reaches the attention norm's gain through the
    route (the experts multiply other rows), and with the router reading the
    FFN's input it does not: the gain's gradient differs."""
    model = _model()
    params = init_params(TransformerConfig(**model), seed=3)
    tokens = _tokens()
    _, early = _loss_and_grads(model, params, tokens)
    _, late = _loss_and_grads(dict(model, router_input="mlp"), params, tokens)
    lyr = lambda g: smallthinker_lm.layer(g["layers"], 0)
    assert float(jnp.max(jnp.abs(lyr(early)["router"]))) > 0
    assert _rel(lyr(early)["attn_norm"], lyr(late)["attn_norm"]) > 1e-3


# ------------------------------------------------------------ ReLU experts
def test_an_inactive_relu_unit_has_exactly_zero_gradient():
    """One expert, every token routed to it, one hidden unit whose ``w1``
    column makes its pre-activation negative for every token: that column
    of ``w1`` and ``w3`` and that row of ``w2`` get exactly zero, in the
    program's routed FFN and in the reference's."""
    key = jax.random.key(7)
    x = jnp.abs(jax.random.normal(key, (1, 12, 8), jnp.float32)) + 0.1
    params = init_moe_params(8, 4, 2, seed=1)
    params["w1"] = params["w1"].at[:, :, 2].set(-1.0)     # unit 2 inactive

    def program(p):
        out, *_ = moe_ffn(p, x, top_k=2, dispatch="grouped", aux=False,
                          act="relu")
        return jnp.sum(out * out)

    def reference(p):
        u = x.reshape(12, 8)
        st = {"routing_dtype": None, "top_k": 2, "act": "relu"}
        weights, experts = smallthinker_lm._route(u, p["router"], st)
        y = smallthinker_lm._experts(u, p, weights, experts, st)
        return jnp.sum(y * y)

    for grads in (jax.grad(program)(params), jax.grad(reference)(params)):
        assert float(jnp.max(jnp.abs(grads["w1"][:, :, 2]))) == 0.0
        assert float(jnp.max(jnp.abs(grads["w3"][:, :, 2]))) == 0.0
        assert float(jnp.max(jnp.abs(grads["w2"][:, 2, :]))) == 0.0
        assert float(jnp.max(jnp.abs(grads["w1"][:, :, 0]))) > 0.0
    silu = jax.grad(lambda p: jnp.sum(moe_ffn(
        p, x, top_k=2, dispatch="grouped", aux=False)[0] ** 2))(params)
    assert float(jnp.max(jnp.abs(silu["w1"][:, :, 2]))) > 0.0


def test_relu_gates_the_dense_and_the_shared_ffn_too():
    tokens = _tokens(seq=16)
    dense = dict(vocab_size=96, dim=32, n_layers=2, n_heads=4, hidden=16,
                 max_seq=64)
    params = init_params(TransformerConfig(**dense), seed=1)
    a = lm_loss(params, tokens, TransformerConfig(**dense))
    b = lm_loss(params, tokens, TransformerConfig(**dense, ffn_act="relu"))
    assert abs(float(a) - float(b)) > 1e-4
    shared = _model(shared_expert_hidden=16)
    params = init_params(TransformerConfig(**shared), seed=1)
    a = lm_loss(params, tokens, TransformerConfig(**shared))
    b = lm_loss(params, tokens, TransformerConfig(
        **dict(shared, ffn_act="silu")))
    assert abs(float(a) - float(b)) > 1e-4


# ----------------------------------------------------- what the step traces
def _lowered_text(model, tokens):
    cfg = TransformerConfig(**model)
    params = jax.eval_shape(lambda: init_params(cfg, seed=0))
    return jax.jit(lambda p, t: jax.value_and_grad(
        lambda p: lm_loss(p, t, cfg))(p)).lower(params, tokens).as_text()


def test_the_unrotated_kind_traces_no_angle():
    tokens = _tokens()
    only_nope = _model(layer_types=[NOPE] * 4, layer_period=0)
    text = _lowered_text(only_nope, tokens)
    assert "cosine" not in text and "sine" not in text
    rotated = _lowered_text(_model(), tokens)
    assert "cosine" in rotated and "sine" in rotated
    assert TransformerConfig(**only_nope).layout.uniform


def test_the_step_counts_and_names_what_it_traced(monkeypatch):
    def counted():
        return {k: v["value"] for k, v in metrics.snapshot().items()
                if "value" in v}

    before = counted()
    model = _model()
    cfg = TransformerConfig(**model)
    names = []
    real = jax.named_scope
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: (names.append(name), real(name))[1])
    jax.eval_shape(lambda p, t: lm_loss(p, t, cfg),
                   jax.eval_shape(lambda: init_params(cfg, seed=0)),
                   _tokens())
    assert {"route_early", "moe.route", "attn", "attn.full_nope",
            "attn.sliding", "moe.experts"} <= set(names)
    # the route is made before the layer's heads: after the block's first
    # ``attn`` (the norm) comes ``route_early``, then ``attn`` again
    first = names.index("route_early")
    assert names[first + 1] == "moe.route" and "attn" in names[:first]
    assert names[first + 2:].index("attn") < names[first + 2:].index("mlp")
    after = counted()
    grew = {k for k in after if after[k] > before.get(k, 0)}
    assert 'attention.nope_traced{heads="14"}' in grew
    assert ('moe.traced{act="relu",dispatch="grouped",router_input="attn"}'
            in grew)
    assert 'attention.window_traced{window="16"}' in grew


def test_the_new_fields_at_their_defaults_are_the_old_program():
    base = dict(vocab_size=96, dim=32, n_layers=2, n_heads=4, hidden=16,
                max_seq=64, num_experts=4, top_k=2, moe_dispatch="grouped")
    tokens = _tokens(seq=16)
    assert _lowered_text(base, tokens) == _lowered_text(
        dict(base, router_input="mlp", ffn_act="silu"), tokens)


@pytest.mark.parametrize("over,match", [
    ({"router_input": "raw"}, "router_input"),
    ({"router_input": "attn", "hc_mult": 2}, "router_input"),
    ({"router_input": "attn", "num_experts": 0, "layer_types": None,
      "layer_period": 0}, "router_input"),
    ({"ffn_act": "gelu"}, "ffn_act"),
    ({"layer_types": ["full_attention_rope"] * 4}, "unknown layer kind")])
def test_the_configuration_refuses_by_name(over, match):
    with pytest.raises(ValueError, match=match):
        TransformerConfig(**_model(**over))


def test_the_trainer_steps_the_model_and_the_loss_falls():
    model = _model()
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1), ("dp",))
    trainer = TransformerTrainer(
        TransformerConfig(**model), mesh, updater_type="sgd",
        option=AddOption(learning_rate=0.05), seed=2)
    tokens = np.asarray(_tokens())
    losses = [float(trainer.train_step_async(tokens)) for _ in range(4)]
    assert losses[-1] < losses[0] and np.all(np.isfinite(losses))
    assert trainer.routes is None          # every expert held: none counted


# ------------------------------------- a group of 7 and a window of 4,096
def _dense_attention(q, k, v, window=None):
    """Dense masked softmax attention, one query head at a time (a head's
    scores at 6,144 tokens are 144 MiB)."""
    B, H, T, D = q.shape
    group = H // k.shape[1]
    t = jnp.arange(T)
    visible = t[None, :] <= t[:, None]
    if window is not None:
        visible = visible & (t[None, :] > t[:, None] - window)

    def one_head(j):
        s = jnp.einsum("btd,bsd->bts", q[:, j], k[:, j // group]) * D ** -0.5
        p = jax.nn.softmax(jnp.where(visible, s, -jnp.inf), axis=-1)
        return jnp.einsum("bts,bsd->btd", p, v[:, j // group])

    return jax.lax.map(jax.checkpoint(one_head),
                       jnp.arange(H)).transpose(1, 0, 2, 3)


@pytest.mark.parametrize("seq,window,fused", [
    (256, None, True), (256, None, False),
    (6144, 4096, True), (6144, 4096, False)])
def test_seven_heads_a_group_through_the_kernels(seq, window, fused,
                                                 monkeypatch):
    """7 query heads a K/V head, full causal and under a 4,096-key window
    (at 6,144 tokens, so the band cuts keys), in interpret mode against
    dense attention: forward, and dq, dk, dv from the fused backward and from
    the dq + dkv pair both."""
    if not fused:
        monkeypatch.setattr(fa, "_fused_fits", lambda *a: False)
    D = 8 if window else 16
    key = jax.random.split(jax.random.key(seq), 4)
    q = jax.random.normal(key[0], (1, 7, seq, D), jnp.float32)
    k = jax.random.normal(key[1], (1, 1, seq, D), jnp.float32)
    v = jax.random.normal(key[2], (1, 1, seq, D), jnp.float32)
    w = jax.random.normal(key[3], (1, 7, seq, D), jnp.float32)
    before = metrics.counter("attention.bwd_traced",
                             {"path": "fused" if fused else "split"}).value

    def kernel(q, k, v):
        return jnp.sum(w * fa.flash_attention(q, k, v, interpret=True,
                                              window=window))

    def dense(q, k, v):
        return jnp.sum(w * _dense_attention(q, k, v, window))

    got = jax.value_and_grad(kernel, argnums=(0, 1, 2))(q, k, v)
    want = jax.value_and_grad(dense, argnums=(0, 1, 2))(q, k, v)
    assert metrics.counter("attention.bwd_traced", {
        "path": "fused" if fused else "split"}).value == before + 1
    assert abs(float(got[0]) - float(want[0])) < 1e-2 * abs(float(want[0]))
    for mine, theirs in zip(got[1], want[1]):
        assert _rel(mine, theirs) < 1e-4


def test_the_cells_shape_is_past_the_fused_backward():
    """16,384 tokens at D=128 with grouped heads: ``_fused_fits`` turns the
    fused backward away (dk and dv of a K/V head resident beside dq), full
    and windowed alike; 8,192 tokens still fit."""
    assert not fa._fused_fits(16384, 16384, 128, 7, jnp.bfloat16, 1024)
    assert not fa._fused_fits(16384, 16384, 128, 7, jnp.bfloat16, 512)
    assert fa._fused_fits(8192, 8192, 128, 7, jnp.bfloat16, 1024)
    assert fa._fused_fits(16384, 16384, 128, 1, jnp.bfloat16, 1024)


# ------------------------------------------------------------- hand counts
def _published_model():
    with open(CONFIG) as f:
        return json.load(f)["model"]


def test_the_pairs_are_counted_as_a_brute_force_mask_counts_them():
    for seq, window in ((64, 16), (64, 64), (48, 100), (33, 1)):
        t = np.arange(seq)
        mask = (t[None, :] <= t[:, None]) & (t[None, :] > t[:, None] - window)
        assert counts.attention_pairs(seq, window) == int(mask.sum())
    assert counts.attention_pairs(16384) == 134_225_920
    assert counts.attention_pairs(16384, 4096) == 58_722_304


def test_the_step_is_counted_by_hand():
    m = _published_model()
    T = 16384
    assert counts.layers_of(m) == 4 and counts.layers_of(m, counts.NOPE) == 1
    assert counts.layers_of(m, counts.SLIDING) == 3
    attn = 2 * 2560 * 3584 + 2 * 2560 * 512                  # a layer's
    assert attn == 20_971_520
    assert counts.token_matmul_params(m) == (
        4 * (attn + 2560 * 64) + 18992 * 2560)
    assert counts.routes(m, T) == 98_304
    assert counts.routed_flops(m, T) == (
        4 * 9 * 2.0 * 98_304 * 2560 * 768)
    # the issue's arithmetic: 13.9 TFLOP of routed experts a step
    assert abs(counts.routed_flops(m, T) / 1e12 - 13.9) < 0.05
    full = counts.attention_flops(m, 1, T, counts.NOPE)
    assert full == 3 * 4.0 * 28 * 134_225_920 * 128
    assert counts.attention_flops(m, 1, T, counts.NOPE, "fwd") == full / 3
    assert counts.attention_flops(m, 1, T, counts.NOPE, "bwd") == 2 * full / 3
    win = counts.attention_flops(m, 1, T, counts.SLIDING)
    assert win == 3 * 3 * 4.0 * 28 * 58_722_304 * 128
    assert counts.train_flops(m, 1, T) == (
        6.0 * counts.token_matmul_params(m) * T
        + counts.routed_flops(m, T) + full + win)
    q, kv, stats = 28 * T * 128 * 2, 4 * T * 128 * 2, 28 * T * 4
    assert counts.flash_bytes(m, 1, T, counts.NOPE) == {
        "fwd": 2 * q + 2 * kv + stats, "bwd": 4 * q + 4 * kv + stats}
    assert counts.flash_bytes(m, 1, T, counts.SLIDING)["fwd"] == 3 * (
        2 * q + 2 * kv + stats)
    assert counts.grouped_matmul_bytes(m, T) == (
        4 * 9.0 * (64 * 2560 * 768 + 98_304 * (2560 + 768)) * 2)


# ------------------------------------------------------- the configuration
def test_the_configuration_file_agrees_with_itself_and_the_catalog():
    with open(CONFIG) as f:
        config = json.load(f)
    from benchmarks.harness import load_module
    from benchmarks.harness import HERE
    runner = load_module((HERE,), "runners", "lm_train_route_first")
    runner._check_published(config)
    model = config["model"]
    assert (config["hidden_size"], config["num_attention_heads"],
            config["num_key_value_heads"], config["head_dim"]) == (
                2560, 28, 4, 128)
    assert (config["moe_num_primary_experts"], config["moe_ffn_hidden_size"],
            config["moe_num_active_primary_experts"]) == (64, 768, 6)
    assert (config["sliding_window_size"], config["rope_theta"],
            config["rms_norm_eps"], config["tie_word_embeddings"],
            config["max_position_embeddings"]) == (4096, 1500000, 1e-6,
                                                   False, 16384)
    assert set(config["reduced"]) == {"num_hidden_layers", "rope_layout",
                                      "sliding_window_layout", "vocab_size"}
    assert config["published"]["vocab_size"] == 8 * config["vocab_size"]
    assert "settle_steps" not in config["trainer"]
    assert set(config["assumed"]) >= {
        "router_input", "secondary_experts", "activation", "window",
        "bias_and_qk_norm", "aux_loss", "optimizer", "not_consulted"}
    # 1,691,752,960 parameters are what ``init_params`` draws
    cfg = TransformerConfig(**model)
    shapes = jax.eval_shape(lambda: init_params(cfg, seed=0))
    assert sum(int(np.prod(leaf.shape)) for leaf in
               jax.tree_util.tree_leaves(shapes)) == 1_691_752_960
    lay = cfg.layout
    assert (lay.lead, lay.n_periods, lay.n_trail) == ((), 1, 0)
    assert [k.attn for k in lay.period] == PERIOD
    if not os.path.isfile(CATALOG):
        return
    with open(CATALOG) as f:
        row, = [r for r in map(json.loads, f)
                if r["source_url"] == config["source"]]
    for key, value in row["config"].items():
        if key not in config["reduced"]:
            assert config[key] == value, key
        else:
            assert config[key] == (value[:4] if isinstance(value, list)
                                   else config[key])
    assert config["published"]["num_hidden_layers"] == row["layers"] == 52
    assert config["published"]["vocab_size"] == row["vocab_size"]
    wrong = dict(config, rope_layout=[1, 1, 1, 1])
    with pytest.raises(ValueError, match="rope_layout"):
        runner._check_published(wrong)


def test_the_benchmark_lists_the_cell_exactly_where_its_readers_apply():
    from benchmarks import harness
    from benchmarks.tests.tiny import workloads_by_applies

    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    row = bench["workloads"][9]                 # later PRs' cells follow
    assert row == {
        "name": CELL, "config": "smallthinker-21b-a3b-l4-e64",
        "traffic": "zipf-seq16k-b1-chk8k", "chips": 1, "why": row["why"]}
    for w in bench["workloads"] + bench["configs"]:
        assert len(w["why"]) <= 200, w["name"]
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    readers = harness.layer_readers((harness.HERE,))
    applies = workloads_by_applies(bench, readers)
    cells = [w["name"] for w in bench["workloads"]]
    mine = {m["name"] for m in bench["per_layer"]
            if CELL in m.get("workloads", cells)}
    assert mine == {name for name, where in applies.items() if CELL in where}
    new = {"model.attn_nope_full_ms_per_step", "model.attn_win4k_ms_per_step",
           "model.moe_route_early_ms_per_step",
           "kernel.flash_gqa7_fwd_roofline",
           "kernel.flash_gqa7_bwd_pair_roofline",
           "kernel.flash_win4k_fwd_roofline",
           "kernel.flash_win4k_bwd_pair_roofline",
           "kernel.moe_gmm_reglu_roofline"}
    assert new <= mine
    for m in bench["per_layer"]:
        if m["name"] in new:
            assert m["workloads"] == [CELL]
            assert m["moves"] == "tokens_per_chip_s"
    moved = {m["name"]: m for m in bench["end_to_end"]}
    assert moved["tokens_per_chip_s"]["workloads"][8] == CELL
    cell = harness.load_cell(CELL)
    assert cell.traffic["check"] == {"batch": 1, "seq": 8192}
    assert (cell.traffic["batch"], cell.traffic["seq"]) == (1, 16384)
    assert {m["name"] for m in cell.end_to_end} == {
        "setup_s", "tokens_per_chip_s", "peak_hbm_gib"}


# ------------------------------------------------- the readers of the trace
def _fake_trace():
    """Two steps' worth of device events on one chip, by hand."""
    from benchmarks.trace import reduce as R

    ms = 1e6
    ops, at = [], 0.0
    index_names = {}
    for step in range(2):
        for name, op_name, dur in (
                ("fusion.1", "jit(step)/jvp(layers)/while/body/attn/"
                 "attn.full_nope/dot_general", 2.0),
                ("fusion.2", "jit(step)/jvp(layers)/while/body/route_early/"
                 "moe.route/dot_general", 0.5),
                ("fusion.3", "jit(step)/transpose(jvp(layers))/while/body/"
                 "route_early/moe.route/top_k", 0.25),
                ("flash_fwd.1", "jit(step)/jvp(layers)/while/body/attn/"
                 "attn.full_nope/flash_fwd/pallas_call", 4.0),
                ("flash_win_bwd_dq.1", "jit(step)/transpose(jvp(layers))/"
                 "while/body/attn/attn.sliding/flash_win_bwd_dq/pallas_call",
                 3.0),
                ("fusion.4", "jit(step)/jvp(layers)/while/body/mlp/"
                 "moe.experts/mul", 1.0)):
            ops.append(R.Event(name, at, at + dur * ms))
            index_names[name] = op_name
            at += dur * ms
    modules = [R.Event("jit_step(1)", 0.0, at / 2),
               R.Event("jit_step(1)", at / 2, at)]
    host = [R.Event(R.WINDOW_SPAN, 0.0, at)]
    trace = R.Trace(host=host, devices={
        "/device:TPU:0": R.DeviceLines(ops=ops, modules=modules)})
    return trace, index_names


def test_the_trace_walk_books_the_three_scopes():
    from benchmarks.trace import program, route_first

    trace, names = _fake_trace()
    index = program.ScopeIndex()
    index.op_names.update(names)
    found = route_first.summarize(trace, index)
    assert found.step_programs == 2
    got = {k: round(v * 1e3, 6) for k, v in found.by_scope_s.items()}
    assert got == {"route_early": 1.5, "attn.full_nope": 12.0,
                   "attn.sliding": 6.0}
    # a program without the scopes (the parent's) gives nothing to read
    bare = program.ScopeIndex()
    bare.op_names.update({k: "jit(step)/jvp(layers)/attn/mul" for k in names})
    assert route_first.summarize(trace, bare) is None


def test_the_readers_leave_out_what_is_not_there():
    from benchmarks import harness

    readers = harness.layer_readers((harness.HERE,))
    off_chip = harness.Reading(facts={}, trace=None, peaks={},
                               compiles_in_window=0)
    for name, r in readers.items():
        if r.APPLIES.get("runner") == "lm_train_route_first":
            assert r.read(off_chip) is None, name
            assert r.SOURCE == "device_trace"


# ------------------------------------------------------ the cell, rehearsed
def test_the_cell_rehearses_at_toy_widths(tmp_path, monkeypatch):
    """The real runner, generator, reference and readers on the cell's own
    files shrunk to toy widths, on the CPU with the kernels interpreted:
    every check but the reference's tolerance holds as on the chip (the
    bounds are the published widths'; the toy's own are wider), and a control
    reads further off than the program."""
    from benchmarks import harness

    monkeypatch.setenv("MVTPU_FORCE_FLASH", "1")
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["paths"] = ["tinybench"]
    with open(CONFIG) as f:
        config = json.load(f)
    config.update(hidden_size=128, head_dim=16, num_attention_heads=14,
                  num_key_value_heads=2, moe_ffn_hidden_size=64,
                  moe_num_primary_experts=8, moe_num_active_primary_experts=3,
                  sliding_window_size=64, vocab_size=512,
                  max_position_embeddings=512)
    config["model"].update(dim=128, head_dim=16, n_heads=14, n_kv_heads=2,
                           hidden=64, num_experts=8, top_k=3,
                           sliding_window=64, vocab_size=512, max_seq=512)
    config["trainer"]["learning_rate"] = 0.02
    for declared in bench["configs"]:
        if declared["name"] == config["name"]:
            declared["file"] = "tinybench/configs/smallthinker.json"
    with open(os.path.join(REPO, "benchmarks", "traffic",
                           "zipf-seq16k-b1-chk8k.json")) as f:
        traffic = json.load(f)
    traffic.update(batch=1, seq=512, check={"batch": 1, "seq": 256},
                   trace_seconds=0.5)
    for path, obj in (("tinybench/configs/smallthinker.json", config),
                      ("tinybench/traffic/zipf-seq16k-b1-chk8k.json",
                       traffic),
                      ("BENCHMARK.json", bench)):
        os.makedirs(os.path.dirname(tmp_path / path), exist_ok=True)
        with open(tmp_path / path, "w") as f:
            json.dump(obj, f)
    cell = harness.load_cell(CELL, root=str(tmp_path))
    assert cell.runner == "lm_train_route_first" and cell.chips == 1
    reference = harness.load_module(cell.search, "reference",
                                    "smallthinker_lm")
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
    check, = [f["reference_check"] for f in logged if "reference_check" in f]
    assert check["shape"] == [1, 256] and check["layers"] == [0, 1]
    # 2 layers x (2 gains, router, 4 projections, 3 expert matrices) + 2
    assert len(check["grad_rel_err"]) == 2 * 10 + 2
    runner = harness.load_module(cell.search, "runners",
                                 "lm_train_route_first")
    assert sorted(k for k in check["grad_rel_err"]
                  if reference.routed(k)) == [
        "L0.router", "L0.w1", "L0.w2", "L0.w3",
        "L1.router", "L1.w1", "L1.w2", "L1.w3"]
    assert set(result["compared"]) == {"loss_abs_err", "worst",
                                       "worst_routed", "compiles_in_window"}
    traced, = [f["traced"] for f in logged if "traced" in f]
    assert traced["jnp"] == 0 and traced["interpret"] >= 1
    assert traced["window"] >= 1 and traced["nope"] >= 1
    assert traced["route_early"] >= 1
    parts, = [f for f in logged if "setup_parts_s" in f]
    assert "settling" not in parts["setup_parts_s"]
    # the same step read against a control's reference lies further off
    assert set(runner.CONTROLS) >= {"router_reads_ffn_input", "silu_for_relu",
                                    "rotary_on_full_layer", "routing_bf16",
                                    "softmax_bf16", "weights_float8"}
    spec = runner._control(runner.CONTROLS["weights_float8"])
    assert spec == {"weights_dtype": jnp.float8_e4m3fn}
