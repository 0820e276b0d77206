"""OLMoE on the normal path (ISSUE 26): the grouped (dropless) expert
schedule, ``norm_topk_prob``, the router z-loss and QK-norm, held to the plain
reference ``benchmarks/reference/olmoe_lm.py`` and to the ``dense`` schedule,
small, on the CPU."""

import json
import os
import re
import sys
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks.reference import olmoe_lm  # noqa: E402
from multiverso_tpu import metrics  # noqa: E402
from multiverso_tpu.models import (TransformerConfig,  # noqa: E402
                                   TransformerTrainer, init_params)
from multiverso_tpu.models.moe import (_combine, _dispatch,  # noqa: E402
                                       _sort_routes,
                                       init_moe_params, moe_ffn)
from multiverso_tpu.models.transformer import (expert_load,  # noqa: E402
                                               lm_loss)

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"

# The two tiny sizes of ISSUE 26: few experts, and OLMoE's own 64 / top-8.
SIZES = {
    "e8k3": dict(vocab_size=96, dim=32, n_layers=2, n_heads=2, hidden=24,
                 max_seq=32, num_experts=8, top_k=3),
    "e64k8": dict(vocab_size=96, dim=32, n_layers=2, n_heads=2, hidden=32,
                  max_seq=32, num_experts=64, top_k=8),
}
OLMOE = dict(qk_norm=True, norm_topk_prob=False, router_z_loss_coef=0.001,
             aux_loss_coef=0.01, moe_dispatch="grouped", rope_theta=1e4,
             norm_eps=1e-5)


def _model(size: str, **over) -> dict:
    return {**SIZES[size], **OLMOE, **over}


def _params(cfg: TransformerConfig, seed: int):
    """Seeded parameters with every norm gain off 1, so a gain that is
    skipped or applied in the wrong place shows."""
    rng = np.random.RandomState(seed + 100)
    params = init_params(cfg, seed=seed)

    def gains(tree):
        for key in tree:
            if key.endswith("norm"):
                tree[key] = (1 + 0.3 * rng.randn(*tree[key].shape)
                             ).astype(np.float32)

    gains(params)
    for lyr in ([params["layers"]] if cfg.scan_layers else params["layers"]):
        gains(lyr)
    return jax.tree_util.tree_map(jnp.asarray, params)


def _tokens(vocab: int, batch: int = 2, seq: int = 32, seed: int = 0):
    return jnp.asarray(np.random.RandomState(seed).randint(
        vocab, size=(batch, seq)).astype(np.int32))


def _rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def _leaf_errors(got, want) -> dict:
    flat, _ = jax.tree_util.tree_flatten_with_path(
        jax.tree_util.tree_map(_rel, got, want))
    return {jax.tree_util.keystr(path): err for path, err in flat}


# ------------------------------------------------- system against reference
@pytest.mark.parametrize("scan", [True, False], ids=["scan", "loop"])
@pytest.mark.parametrize("size", sorted(SIZES))
def test_system_matches_the_reference_in_float32(size, scan):
    """Loss and every gradient leaf to 1e-4 relative, both parameter
    formats."""
    model = _model(size, scan_layers=scan)
    cfg = TransformerConfig(**model, compute_dtype=jnp.float32)
    params = _params(cfg, seed=1)
    tokens = _tokens(cfg.vocab_size)
    got_loss, got = jax.value_and_grad(lm_loss)(params, tokens, cfg)
    want_loss, want = jax.value_and_grad(olmoe_lm.loss)(params, tokens, model)
    assert float(got_loss) == pytest.approx(float(want_loss), rel=1e-5)
    errs = _leaf_errors(got, want)
    assert len(errs) == (3 + 12 if scan else 3 + 12 * cfg.n_layers)
    assert max(errs.values()) < 1e-4, errs


def test_reference_loss_and_grads_is_its_own_gradient():
    """The hand-chained backward the runner calls against ``jax.grad`` of
    the reference's ``loss``."""
    model = _model("e8k3", scan_layers=True)
    cfg = TransformerConfig(**model, compute_dtype=jnp.float32)
    params, tokens = _params(cfg, seed=2), _tokens(cfg.vocab_size)
    want_loss, want = jax.value_and_grad(olmoe_lm.loss)(params, tokens, model)
    got_loss, got = olmoe_lm.loss_and_grads(params, tokens, model, layer=1)
    assert float(got_loss) == pytest.approx(float(want_loss), rel=1e-6)
    assert _rel(got["embed"], want["embed"]) < 1e-5
    assert _rel(got["out_norm"], want["out_norm"]) < 1e-5
    assert set(got["layer"]) == set(want["layers"])
    for leaf, grad in got["layer"].items():
        assert _rel(grad, want["layers"][leaf][1]) < 1e-5, leaf


@pytest.mark.parametrize("size", sorted(SIZES))
def test_bfloat16_system_within_the_reference_tolerance(size):
    """The configuration's precision (bfloat16 compute, float32 masters)
    against the float32 reference, on the leaves the runner samples.  The
    loss and the leaves no route reaches are held to the tolerances the
    runner uses on the chip.  The routed leaves (``mlp_norm``, ``w2``) get
    three times ``GRAD_RTOL`` here: at width 32 an expert sees 16 to 48
    rows, so one token whose 8th and 9th experts swap on rounding
    (``olmoe_lm``'s docstring) moves a visible share of an expert's
    gradient; measured over three seeds 2-9% (e8k3) and 10-14% (e64k8),
    against 1.4-2.4% at the published widths on the chip, where the
    runner's own bound holds."""
    model = _model(size, scan_layers=True)
    cfg = TransformerConfig(**model)
    assert cfg.compute_dtype == jnp.bfloat16
    params = _params(cfg, seed=3)
    tokens = _tokens(cfg.vocab_size, batch=4, seed=3)
    got_loss, got = jax.value_and_grad(lm_loss)(params, tokens, cfg)
    want_loss, want = olmoe_lm.loss_and_grads(params, tokens, model, layer=1)
    assert abs(float(got_loss) - float(want_loss)) < olmoe_lm.LOSS_ATOL
    errs = {"embed": _rel(got["embed"], want["embed"]),
            "out_norm": _rel(got["out_norm"], want["out_norm"])}
    for leaf in ("attn_norm", "wq", "mlp_norm", "w2"):
        errs[leaf] = _rel(got["layers"][leaf][1], want["layer"][leaf])
    routed = {k: errs.pop(k) for k in ("mlp_norm", "w2")}
    assert max(errs.values()) < olmoe_lm.GRAD_RTOL, errs
    assert max(routed.values()) < 3 * olmoe_lm.GRAD_RTOL, routed


def test_reference_router_input_rounding_option():
    """``router_input_dtype`` rounds what the router sees and nothing
    else: with a zero router every probability is 1/E whatever the input,
    so the option must change nothing; with a real router it moves the
    loss by a rounding's worth."""
    model = _model("e8k3", scan_layers=True)
    cfg = TransformerConfig(**model, compute_dtype=jnp.float32)
    params, tokens = _params(cfg, seed=4), _tokens(cfg.vocab_size)
    plain = float(olmoe_lm.loss(params, tokens, model))
    rounded = float(olmoe_lm.loss(params, tokens, model,
                                  router_input_dtype=jnp.bfloat16))
    assert plain != rounded and abs(plain - rounded) < 1e-2
    flat = dict(params, layers=dict(
        params["layers"], router=jnp.zeros_like(params["layers"]["router"])))
    assert float(olmoe_lm.loss(flat, tokens, model)) == pytest.approx(
        float(olmoe_lm.loss(flat, tokens, model,
                            router_input_dtype=jnp.bfloat16)), rel=1e-7)


# ------------------------------------------------------ grouped vs the oracle
def _ffn_case(E=8, k=3, dim=16, hidden=24, tokens=(2, 24), seed=0):
    params = init_moe_params(dim=dim, hidden=hidden, num_experts=E, seed=seed)
    x = jnp.asarray(np.random.RandomState(seed + 1).randn(*tokens, dim)
                    .astype(np.float32) * 0.7)
    return jax.tree_util.tree_map(jnp.asarray, params), x


@pytest.mark.parametrize("norm_topk_prob", [True, False])
def test_grouped_equals_dense_values_and_gradients(norm_topk_prob):
    params, x = _ffn_case()

    def run(dispatch):
        def f(p, x):
            out, balance, z, *_ = moe_ffn(p, x, top_k=3, dispatch=dispatch,
                                          norm_topk_prob=norm_topk_prob)
            return (jnp.sum(jnp.sin(out)) + 0.3 * balance + 0.2 * z,
                    (out, balance, z))

        (_, outs), grads = jax.value_and_grad(f, argnums=(0, 1),
                                              has_aux=True)(params, x)
        return outs, grads

    (out_g, bal_g, z_g), grads_g = run("grouped")
    (out_d, bal_d, z_d), grads_d = run("dense")
    np.testing.assert_allclose(out_g, out_d, atol=2e-6)
    assert float(bal_g) == pytest.approx(float(bal_d), rel=1e-6)
    assert float(z_g) == pytest.approx(float(z_d), rel=1e-6)
    errs = _leaf_errors(grads_g, grads_d)
    assert max(errs.values()) < 1e-5, errs


def test_norm_topk_prob_against_a_per_token_loop():
    """The k route weights are the router's probabilities as they are
    (OLMoE) or renormalised to sum to 1."""
    params, x = _ffn_case(E=4, k=2, tokens=(1, 6))
    p = {k: np.asarray(v) for k, v in params.items()}
    xs = np.asarray(x)[0]
    probs = np.asarray(jax.nn.softmax(jnp.asarray(xs @ p["router"]), -1))
    for norm in (True, False):
        want = np.zeros_like(xs)
        for t in range(len(xs)):
            idx = np.argsort(-probs[t])[:2]
            w = probs[t, idx] / (probs[t, idx].sum() if norm else 1.0)
            for wj, e in zip(w, idx):
                gate = xs[t] @ p["w1"][e]
                want[t] += wj * ((gate / (1 + np.exp(-gate))
                                  * (xs[t] @ p["w3"][e])) @ p["w2"][e])
        got, *_ = moe_ffn(params, x, top_k=2, dispatch="grouped",
                          norm_topk_prob=norm)
        np.testing.assert_allclose(np.asarray(got)[0], want, atol=1e-5)


def test_no_route_is_dropped_when_one_expert_takes_every_token():
    """The no-drop test: an adversarial router sends all 48 tokens to
    expert 0.  Grouped computes every route (equal to ``dense``): the
    48th token has its output like the first."""
    params, x = _ffn_case(E=8, k=1)
    router = np.zeros((16, 8), np.float32)
    router[:, 0] = 100.0
    params = dict(params, router=jnp.asarray(router))
    x = jnp.abs(x)                                   # positive x => +logit
    dense, *_ = moe_ffn(params, x, top_k=1, dispatch="dense")
    grouped, _, _, load, _ = moe_ffn(params, x, top_k=1, dispatch="grouped")
    assert np.asarray(load).tolist() == [48, 0, 0, 0, 0, 0, 0, 0]
    np.testing.assert_allclose(grouped, dense, atol=2e-6)
    assert np.abs(np.asarray(dense)).min(axis=-1).max() > 0
    per_token = np.abs(np.asarray(grouped).reshape(48, 16)).max(axis=-1)
    assert per_token.min() > 0                       # no token without output


def _eqns(jaxpr):
    """Every equation of a jaxpr, those of its sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def test_grouped_schedule_holds_no_route_by_expert_matrix():
    """No ``[N*k, E]`` (one-hot, cumsum) intermediate: routes are sorted,
    not expanded against the experts, as ``dense``'s one-hot combine
    weights are."""
    params, x = _ffn_case(E=8, k=3, tokens=(2, 24))
    routes, experts = 2 * 24 * 3, 8
    jaxpr = jax.make_jaxpr(
        lambda p, x: moe_ffn(p, x, top_k=3, dispatch="grouped"))(params, x)

    def shapes(jp):
        return (tuple(getattr(v.aval, "shape", ()))
                for eqn in _eqns(jp) for v in eqn.outvars)

    seen = set(shapes(jaxpr.jaxpr))
    assert (routes, 16) in seen                      # the gathered rows
    assert not any(s[:1] == (routes,) and experts in s[1:] for s in seen)
    dense = jax.make_jaxpr(
        lambda p, x: moe_ffn(p, x, top_k=3, dispatch="dense"))(params, x)
    assert (2, 24, 3, experts) in set(shapes(dense.jaxpr))


def _grouped_loss(p, x):
    out, balance, z, *_ = moe_ffn(p, x, top_k=3, dispatch="grouped",
                                  norm_topk_prob=False)
    return jnp.sum(jnp.sin(out)) + 0.3 * balance + 0.2 * z


def test_grouped_schedule_moves_no_row_by_scatter():
    """Forward and backward: rows of width ``D`` go out and come back by
    gathers (a permutation and its inverse), so no ``scatter`` or
    ``scatter-add`` has them as operand or updates.  ``top_k``'s own
    transpose scatters scalars and stays."""
    params, x = _ffn_case(E=8, k=3, dim=16, tokens=(2, 24))
    D, routes = 16, 2 * 24 * 3
    jaxpr = jax.make_jaxpr(jax.grad(_grouped_loss, argnums=(0, 1)))(params, x)

    def last_dims(eqn):
        return [v.aval.shape[-1] for v in (*eqn.invars, *eqn.outvars)
                if getattr(v.aval, "shape", ())]

    found = list(_eqns(jaxpr.jaxpr))
    scatters = [e for e in found if e.primitive.name.startswith("scatter")]
    assert not [e for e in scatters if D in last_dims(e)], scatters
    # out by ``order``, back by ``inv``, and the two transposes
    row_gathers = [e for e in found if e.primitive.name == "gather"
                   and e.outvars[0].aval.shape in ((routes, D),
                                                   (routes // 3, 3, D))]
    assert len(row_gathers) >= 4
    # the walk does see a row scatter where there is one: the plain spelling
    plain = jax.make_jaxpr(jax.grad(
        lambda x, token: jnp.sum(jnp.sin(x[token]))))(
            x.reshape(-1, D), jnp.arange(routes) // 3)
    assert [e for e in _eqns(plain.jaxpr)
            if e.primitive.name.startswith("scatter") and D in last_dims(e)]


def _routes(k: int, collapsed: bool, N=24, E=8, seed=0):
    """``(order, inv [N, k])`` of a random router's top-k, or of one that
    sends every route to expert 0."""
    rng = np.random.RandomState(seed)
    if collapsed:
        top_idx = jnp.zeros((N, k), jnp.int32)
    else:
        _, top_idx = jax.lax.top_k(jnp.asarray(rng.randn(N, E)), k)
    sorted_expert, order, inv = _sort_routes(top_idx)
    expert = top_idx.reshape(-1)
    np.testing.assert_array_equal(order, jnp.argsort(expert, stable=True))
    np.testing.assert_array_equal(sorted_expert, expert[order])
    return order, inv


@pytest.mark.parametrize("collapsed", [False, True],
                         ids=["random", "collapsed"])
@pytest.mark.parametrize("k", [1, 3, 8])
def test_routes_go_out_and_come_back_as_permutations(k, collapsed):
    """``x[order][inv] == x``, and the hand-written transposes of
    ``_dispatch`` and ``_combine`` equal autodiff's of the plain spelling
    (a gather by token, ``zeros.at[token].add``) to float32 round-off."""
    N, D = 24, 16
    order, inv = _routes(k, collapsed, N=N)
    rng = np.random.RandomState(k)
    routes = jnp.arange(N * k)
    np.testing.assert_array_equal(routes[order][inv.reshape(-1)], routes)
    np.testing.assert_array_equal(inv.reshape(-1)[order], routes)
    if collapsed:                     # stable: one group keeps token order
        np.testing.assert_array_equal(order, routes)
    token = order // k

    x, d_rows = (jnp.asarray(rng.randn(*shape).astype(np.float32))
                 for shape in ((N, D), (N * k, D)))
    rows, pull = jax.vjp(lambda x: _dispatch(x, order, inv), x)
    want_rows, want_pull = jax.vjp(lambda x: x[token], x)
    np.testing.assert_array_equal(rows, want_rows)
    np.testing.assert_allclose(pull(d_rows)[0], want_pull(d_rows)[0],
                               rtol=1e-6, atol=1e-6)

    down, top_p, d_out = (jnp.asarray(rng.randn(*shape).astype(np.float32))
                          for shape in ((N * k, D), (N, k), (N, D)))

    def plain(down, top_p):
        weight = top_p.reshape(-1)[order]
        return jnp.zeros((N, D), jnp.float32).at[token].add(
            down * weight[:, None])

    out, pull = jax.vjp(lambda d, p: _combine(d, p, order, inv, jnp.float32),
                        down, top_p)
    want_out, want_pull = jax.vjp(plain, down, top_p)
    np.testing.assert_allclose(out, want_out, rtol=1e-6, atol=1e-6)
    for got, want in zip(pull(d_out), want_pull(d_out)):
        assert got.shape == want.shape and got.dtype == want.dtype
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


def test_dispatch_transpose_sums_a_tokens_rows_in_float32():
    """bfloat16 rows: a token's ``k`` cotangent rows are added in float32
    and rounded once."""
    N, D, k = 24, 16, 8
    order, inv = _routes(k, collapsed=False, N=N)
    rng = np.random.RandomState(3)
    d_rows = jnp.asarray(rng.randn(N * k, D), jnp.bfloat16)
    x = jnp.zeros((N, D), jnp.bfloat16)
    got = jax.vjp(lambda x: _dispatch(x, order, inv), x)[1](d_rows)[0]
    want = np.zeros((N, D), np.float32)
    np.add.at(want, np.asarray(order) // k, np.asarray(d_rows, np.float32))
    assert got.dtype == jnp.bfloat16
    np.testing.assert_array_equal(got, jnp.asarray(want).astype(jnp.bfloat16))


def test_combine_in_bfloat16_is_the_plain_spelling_bit_for_bit():
    """bfloat16 rows and a bfloat16 output, as the cell runs: the cast to
    the output's type sits inside ``_combine`` so that its transpose gathers
    ``d_out`` as bfloat16 rows; value and ``d_down`` equal the plain
    spelling's (float32 ``zeros.at[token].add``, cast outside) to the bit,
    ``d_top_p`` to float32 round-off."""
    N, D, k = 24, 16, 8
    order, inv = _routes(k, collapsed=False, N=N)
    token = order // k
    rng = np.random.RandomState(4)
    down = jnp.asarray(rng.randn(N * k, D), jnp.bfloat16)
    top_p = jnp.asarray(rng.rand(N, k).astype(np.float32))
    d_out = jnp.asarray(rng.randn(N, D), jnp.bfloat16)

    def plain(down, top_p):
        weight = top_p.reshape(-1)[order]
        return jnp.zeros((N, D), jnp.float32).at[token].add(
            down.astype(jnp.float32) * weight[:, None]).astype(jnp.bfloat16)

    out, pull = jax.vjp(lambda d, p: _combine(d, p, order, inv, jnp.bfloat16),
                        down, top_p)
    want_out, want_pull = jax.vjp(plain, down, top_p)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want_out, np.float32),
                               rtol=2 ** -7)      # sum order: a last bit
    (d_down, d_top_p), (want_down, want_top_p) = pull(d_out), want_pull(d_out)
    assert d_down.dtype == jnp.bfloat16
    np.testing.assert_array_equal(d_down, want_down)
    np.testing.assert_allclose(d_top_p, want_top_p, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("scope", ["moe.dispatch", "moe.combine"])
def test_backward_rules_open_their_own_scope(scope):
    """Called under no scope at all, each hand-written transpose still
    books its rows to ``moe.dispatch`` / ``moe.combine``: the chip trace's
    ``model.moe_dispatch_ms_per_step`` goes on seeing the backward."""
    N, D, k = 24, 16, 3
    order, inv = _routes(k, collapsed=False, N=N)
    if scope == "moe.dispatch":
        f = jax.grad(lambda x: jnp.sum(jnp.sin(_dispatch(x, order, inv))))
        args = (jnp.ones((N, D)),)
    else:
        f = jax.grad(lambda d, p: jnp.sum(jnp.sin(_combine(d, p, order, inv,
                                                            jnp.float32))),
                     argnums=(0, 1))
        args = (jnp.ones((N * k, D)), jnp.ones((N, k)))
    op_names = set(re.findall(r'op_name="([^"]*)"',
                              jax.jit(f).lower(*args).compile().as_text()))
    part = re.compile(rf"transpose\(.*[/(]{re.escape(scope)}[/)].*gather")
    assert any(part.search(n) for n in op_names), op_names
    assert not any("scatter" in n for n in op_names), op_names


def test_auxiliary_terms_by_hand():
    """A zero router: every probability 1/E, so the z-loss is log(E)^2, and
    top-k picks experts 0..k-1 for every token: f = 1 on those, P = 1/E,
    balance = E * k * (1/E) = k."""
    params, x = _ffn_case(E=8, k=3)
    flat = dict(params, router=jnp.zeros((16, 8)))
    for dispatch in ("grouped", "dense"):
        _, balance, z, load, _ = moe_ffn(flat, x, top_k=3,
                                         dispatch=dispatch)
        assert float(balance) == pytest.approx(3.0, rel=1e-6)
        assert float(z) == pytest.approx(np.log(8.0) ** 2, rel=1e-6)
        assert np.asarray(load).tolist() == [48, 48, 48, 0, 0, 0, 0, 0]
    # and each enters the loss linearly, with its own coefficient
    model = _model("e8k3", scan_layers=True)
    cfg = TransformerConfig(**model, compute_dtype=jnp.float32)
    params, tokens = _params(cfg, seed=5), _tokens(cfg.vocab_size)

    def loss(balance_coef, z_coef):
        return float(lm_loss(params, tokens, replace(
            cfg, aux_loss_coef=balance_coef, router_z_loss_coef=z_coef)))

    base = loss(0.0, 0.0)
    z_term, balance_term = loss(0.0, 1.0) - base, loss(1.0, 0.0) - base
    assert z_term > 0 and balance_term >= 2 * 3 * 0.99   # layers x top_k
    assert loss(0.01, 0.001) == pytest.approx(
        base + 0.01 * balance_term + 0.001 * z_term, rel=1e-6)


# ----------------------------------------------------------------- QK-norm
def test_qk_norm_gains_reach_the_loss_as_in_the_reference():
    model = _model("e8k3", scan_layers=True)
    cfg = TransformerConfig(**model, compute_dtype=jnp.float32)
    params, tokens = _params(cfg, seed=6), _tokens(cfg.vocab_size)
    assert params["layers"]["q_norm"].shape == (2, 32)
    scaled = dict(params, layers=dict(
        params["layers"], q_norm=params["layers"]["q_norm"] * 1.7,
        k_norm=params["layers"]["k_norm"] * 0.6))
    a, b = float(lm_loss(params, tokens, cfg)), float(
        lm_loss(scaled, tokens, cfg))
    assert abs(a - b) > 1e-4
    assert b == pytest.approx(float(olmoe_lm.loss(scaled, tokens, model)),
                              rel=1e-5)
    # without the flag there is no such leaf and the projections go unnormed
    plain = replace(cfg, qk_norm=False)
    assert "q_norm" not in init_params(plain)["layers"]
    assert float(lm_loss(params, tokens, plain)) == pytest.approx(
        float(olmoe_lm.loss(params, tokens, dict(model, qk_norm=False))),
        rel=1e-5)


def test_qk_norm_over_a_tp_sharded_projection():
    """Under ``tp`` wq/wk are column-sharded and the norm runs over the
    whole projection: GSPMD completes the mean of squares across chips."""
    model = _model("e8k3", scan_layers=True)
    cfg = TransformerConfig(**model, compute_dtype=jnp.float32)
    tokens = np.asarray(_tokens(cfg.vocab_size))
    one = TransformerTrainer(cfg, Mesh(np.asarray(jax.devices()[:1]),
                                       ("dp",)), seed=7)
    two = TransformerTrainer(cfg, Mesh(np.asarray(jax.devices()[:2]),
                                       ("tp",)), seed=7)
    assert two.params["layers"]["wq"].sharding.spec[2] == "tp"
    assert one.loss(tokens) == pytest.approx(two.loss(tokens), rel=1e-5)


# --------------------------------------------------- the normal training path
@pytest.mark.parametrize("policy", ["dots", "full"])
def test_remat_gives_the_gradients_of_no_remat(policy):
    model = _model("e8k3", scan_layers=True)
    cfg = TransformerConfig(**model, compute_dtype=jnp.float32)
    params, tokens = _params(cfg, seed=8), _tokens(cfg.vocab_size)
    want = jax.grad(lm_loss)(params, tokens, cfg)
    got = jax.grad(lm_loss)(params, tokens, replace(
        cfg, remat=True, remat_policy=policy))
    errs = _leaf_errors(got, want)
    assert max(errs.values()) < 1e-5, errs


def test_dots_policy_saves_the_grouped_matmuls():
    """A grouped matmul is no ``dot_general``: policy "dots" keeps the
    three outputs by name, so the backward holds no forward grouped matmul
    again (6 = the transposes of three); "full" recomputes all three."""
    model = _model("e8k3", scan_layers=True, remat=True)

    def ragged_dots(policy):
        cfg = TransformerConfig(**model, remat_policy=policy,
                                compute_dtype=jnp.float32)
        params, tokens = _params(cfg, seed=8), _tokens(cfg.vocab_size)
        text = str(jax.make_jaxpr(
            jax.grad(lambda p: lm_loss(p, tokens, cfg)))(params))
        return text.count(" = ragged_dot_general[")

    assert ragged_dots("dots") == 3 + 6
    assert ragged_dots("full") == 3 + 3 + 6


def test_trainer_trains_saves_and_restores_the_new_leaves(mv, tmp_path):
    mv.init()
    model = _model("e8k3", scan_layers=True, remat=True, remat_policy="dots")
    cfg = TransformerConfig(**model)
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("dp",))
    before = metrics.counter("moe.traced", {"dispatch": "grouped"}).value
    tr = TransformerTrainer(cfg, mesh, seed=9)
    tokens = np.asarray(_tokens(cfg.vocab_size, batch=4))
    first = tr.train_step(tokens)
    for _ in range(8):
        last = tr.train_step(tokens)
    assert last < first
    assert metrics.counter("moe.traced",
                           {"dispatch": "grouped"}).value > before
    path = str(tmp_path / "olmoe.ckpt")
    tr.save(path)
    kept = jax.tree_util.tree_map(np.asarray, tr.params)
    tr.train_step(tokens)
    tr.restore(path)
    for leaf in ("q_norm", "k_norm", "router", "w1", "w3", "w2"):
        np.testing.assert_array_equal(np.asarray(tr.params["layers"][leaf]),
                                      kept["layers"][leaf])
    other = TransformerTrainer(cfg, Mesh(np.asarray(jax.devices()[:1]),
                                         ("dp",)), seed=0)
    other.restore(path)
    assert other.loss(tokens) == pytest.approx(tr.loss(tokens), rel=1e-5)


def test_grouped_refuses_an_ep_axis_by_name():
    """No silent fallback to another schedule on an expert-parallel mesh."""
    cfg = TransformerConfig(**_model("e8k3"))
    mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2), ("dp", "ep"))
    tr = TransformerTrainer(cfg, mesh, seed=0)
    with pytest.raises(ValueError, match="'ep' mesh axis"):
        tr.train_step(np.asarray(_tokens(cfg.vocab_size)))
    dense = TransformerTrainer(replace(cfg, moe_dispatch="dense"), mesh,
                               seed=0)
    assert np.isfinite(dense.train_step(np.asarray(_tokens(cfg.vocab_size))))


@pytest.mark.parametrize("dispatch", ["grouped", "dense"])
def test_expert_load_counts_every_route(dispatch):
    model = _model("e64k8", scan_layers=True, moe_dispatch=dispatch)
    cfg = TransformerConfig(**model, compute_dtype=jnp.float32)
    params, tokens = _params(cfg, seed=10), _tokens(cfg.vocab_size)
    load = np.asarray(expert_load(params, tokens, cfg))
    assert load.shape == (2, 64) and load.dtype == np.int32
    assert load.sum(axis=1).tolist() == [2 * 32 * 8] * 2
    loop = replace(cfg, scan_layers=False)
    unstacked = dict(params, layers=[
        {k: v[i] for k, v in params["layers"].items()} for i in range(2)])
    np.testing.assert_array_equal(expert_load(unstacked, tokens, loop), load)
    with pytest.raises(ValueError, match="no experts"):
        expert_load(params, tokens, replace(cfg, num_experts=0))


# ------------------------------------------------------- the benchmark's files
def _configuration() -> dict:
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "olmoe-1b-7b-e64.json")) as f:
        return json.load(f)


def test_configuration_file_agrees_with_itself():
    config = _configuration()
    assert list(config["reduced"]) == ["num_hidden_layers"]
    model = config["model"]
    assert (model["num_experts"], model["top_k"], model["hidden"]) == (
        config["num_experts"], config["num_experts_per_tok"],
        config["intermediate_size"]) == (64, 8, 1024)
    assert model["norm_topk_prob"] is config["norm_topk_prob"] is False
    assert model["qk_norm"] and model["moe_dispatch"] == "grouped"
    assert model["n_layers"] == config["num_hidden_layers"] >= 2
    TransformerConfig(**model)                       # every key is a field


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
    for key, value in rows[config["source"]]["config"].items():
        if key not in config["reduced"]:
            assert config[key] == value, key


def test_zipf_tokens_are_seeded_in_range_and_skewed():
    from benchmarks import harness

    gen = harness.load_module((os.path.join(REPO, "benchmarks"),),
                              "generators", "zipf_tokens")
    traffic = {"batch": 2, "seq": 4096, "zipf_exponent": 1.0}
    seed = 2 ** 31 + 12345                     # the driver's seeds are large
    a = next(gen.batches(traffic, 50304, seed))
    b = next(gen.batches(traffic, 50304, seed))
    c = next(gen.batches(traffic, 50304, seed, stream=1))
    d = next(gen.batches(traffic, 50304, seed + 1))
    assert a.shape == (2, 4096) and a.dtype == np.int32
    assert 0 <= a.min() and a.max() < 50304
    assert (a == b).all() and (a != c).any() and (a != d).any()
    # Zipf(1) over 50,304 ids: the most frequent id holds about 1/ln(V)+...
    # = 8.8% of the draws; a uniform draw would hold 0.002%.
    top = np.bincount(a.ravel()).max() / a.size
    assert 0.06 < top < 0.12
    # ... and it is another id under another seed (a seeded permutation)
    assert np.bincount(a.ravel()).argmax() != np.bincount(d.ravel()).argmax()


def test_grouped_matmul_work_by_hand():
    from benchmarks import flops, flops_moe

    model = dict(dim=2048, hidden=1024, num_experts=64, top_k=8, n_layers=2,
                 n_heads=16, vocab_size=50304)
    routes = 8192 * 8
    assert flops_moe.grouped_matmul_flops(model, 8192) == (
        2 * 3 * 3 * 2 * routes * 2048 * 1024)
    # the active parameters flops.py counts hold the same experts' work
    assert flops.matmul_params(model) == 2 * (
        4 * 2048 ** 2 + 8 * 3 * 2048 * 1024 + 2048 * 64) + 50304 * 2048
    weights = 3 * 64 * 2048 * 1024 * 2
    assert flops_moe.grouped_matmul_bytes(model, 8192) > 2 * 3 * weights


def test_trace_reduction_books_the_moe_scopes_and_the_grouped_matmul():
    """``benchmarks/trace/moe.py`` on hand-made device events: an
    instruction under a ``moe.*`` scope is booked by its ``op_name``
    whatever the phase, the compiler's ``ragged-dot-none`` calls (which
    carry no scope) by their name, and a program without either reads as
    nothing."""
    from benchmarks.trace import moe as trace_moe
    from benchmarks.trace.program import ScopeIndex
    from benchmarks.trace.reduce import DeviceLines, Event, Trace

    us = 1000.0
    call = ('%{} = bf16[8,8]{{1,0}} custom-call(%p), '
            'custom_call_target="tpu_custom_call"')
    ops = [Event("%sort.1 = s32[8]{0} sort(%p)", 0, 10 * us),
           Event("%fusion.2 = f32[8]{0} fusion(%p), kind=kLoop", 10 * us,
                 15 * us),
           Event(call.format("ragged-dot-none.3"), 20 * us, 60 * us),
           Event(call.format("ragged-dot-metadata"), 60 * us, 61 * us),
           Event(call.format("flash_fwd.6"), 61 * us, 71 * us),
           Event("%fusion.9 = f32[8]{0} fusion(%p), kind=kLoop", 71 * us,
                 80 * us),
           Event("%fusion.7 = f32[8]{0} fusion(%p), kind=kLoop", 80 * us,
                 100 * us)]
    index = ScopeIndex()
    index.op_names.update({
        "sort.1": "jit(step)/jvp(layers)/while/body/mlp/moe.dispatch/sort",
        "fusion.2": "jit(step)/transpose(jvp(layers))/while/body/checkpoint/"
                    "rematted_computation/mlp/moe.route/reduce_sum",
        "ragged-dot-none.3": "ragged-dot-none",
        "ragged-dot-metadata": "ragged-dot-metadata",
        "flash_fwd.6": "jit(step)/jvp(layers)/while/body/attn/flash_fwd/"
                       "flash_fwd/pallas_call",
        "fusion.9": "jit(step)/transpose(jvp(layers))/while/body/mlp/"
                    "moe.combine/scatter-add",
        "fusion.7": "jit(step)/jvp(layers)/while/body/mlp/moe.experts/mul"})
    trace = Trace(
        devices={"/device:TPU:0": DeviceLines(
            ops=ops, modules=[Event("jit_step(1)", 0, 50 * us),
                              Event("jit_step(1)", 50 * us, 100 * us)])},
        host=[Event("bench.window", 0, 100 * us)])
    got = trace_moe.summarize(trace, index, cell="a.cell")
    assert got.step_programs == 2 and got.busy_s == pytest.approx(95e-6)
    assert got.by_scope_s == pytest.approx({
        "moe.dispatch": 10e-6, "moe.route": 5e-6, "moe.combine": 9e-6,
        "moe.experts": 61e-6})
    assert got.grouped_matmul_s == pytest.approx(41e-6)
    dense = Trace(devices={"/device:TPU:0": DeviceLines(
        ops=ops[4:5], modules=[])}, host=trace.host)
    assert trace_moe.summarize(dense, index) is None
