"""Mixture-of-Experts tests: routing exactness against a per-token
reference, load-balancing aux-loss behavior, transformer integration,
and an 8-device (dp, sp, tp, ep) expert-parallel training run."""

from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from multiverso_tpu import metrics
from multiverso_tpu.models import moe
from multiverso_tpu.models.moe import (init_moe_params, moe_ffn,
                                       moe_shardings, route_rungs)
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


# ------------------------------- a share's buffers follow the routes it holds
# 64 tokens x top-2 over 8 experts of which (2, 2) are held: 128 routes, an
# even share of 32: a rung of 64 rows and, last, all 128.
_HELD, _ROUTES = (2, 2), 128
# held routes -> the rows the buffers take
_FILLS = {"none": (0, 64), "under": (63, 64), "on": (64, 64),
          "over": (65, 128), "all": (128, 128)}


def _layer_routing_by_hand(held_routes: int, scoring: str, seed: int = 0):
    """A layer's parameters and an input whose first 8 dims say where a token
    goes: ``held_routes`` routes reach experts 2 and 3, the rest go
    elsewhere."""
    E, dim, hidden, N = 8, 16, 8, 64
    rng = np.random.RandomState(seed)
    params = init_moe_params(dim, hidden, E, seed=seed, held=2,
                             scoring=scoring)
    router = 0.02 * rng.randn(dim, E).astype(np.float32)
    router[:E] += np.eye(E, dtype=np.float32)
    params["router"] = router
    x = rng.randn(N, dim).astype(np.float32)
    x[:, :E] *= 0.2
    both = max(held_routes - N, 0)        # tokens with two held routes
    away = (0, 1, 4, 5, 6, 7)
    for n in range(N):
        if n < both:
            picks = (2, 3)
        elif n < held_routes - both:
            picks = (2 + n % 2, away[n % 6])
        else:
            picks = (away[n % 6], away[(n + 1) % 6])
        picks = picks[::-1] if n % 3 == 0 else picks
        x[n, picks[0]], x[n, picks[1]] = 6.0, 5.0
    return (jax.tree_util.tree_map(jnp.asarray, params),
            jnp.asarray(x.reshape(2, N // 2, dim)))


@pytest.mark.parametrize("scoring", ["softmax", "sigmoid"])
@pytest.mark.parametrize("fill", list(_FILLS))
def test_a_shares_buffers_follow_its_held_routes_exactly(fill, scoring):
    """However full the share (no route held; one row under a rung, on it,
    one over it; every route held), the layer and
    its gradients are the ``dense`` oracle's for the same share, the rung
    is the smallest that holds the routes, and ``d_top_p`` is the top
    rung's (the layer over all ``N*k`` rows)."""
    held_routes, rows = _FILLS[fill]
    params, x = _layer_routing_by_hand(held_routes, scoring)
    w = jnp.asarray(np.random.RandomState(9).randn(*x.shape), jnp.float32)
    rungs = route_rungs(_ROUTES, 2, 8)
    assert rows in rungs and rungs[-1] == _ROUTES == 2 * x[..., 0].size
    sigmoid = scoring == "sigmoid"

    def run(params, x, dispatch):
        out, _, _, load, _ = moe_ffn(params, x, top_k=2, dispatch=dispatch,
                                  held=_HELD, routed_scale=2.5, aux=False,
                                  scoring=scoring, all_load=sigmoid)
        return jnp.sum(w * out), (out, load)

    with jax.default_matmul_precision("highest"):
        (_, (got, load)), d_got = jax.value_and_grad(
            run, (0, 1), has_aux=True)(params, x, "grouped")
        (_, (want, _)), d_want = jax.value_and_grad(
            run, (0, 1), has_aux=True)(params, x, "dense")
    load = np.asarray(load)
    assert (load[2:4].sum() if sigmoid else load[:2].sum()) == held_routes
    assert load.sum() == _ROUTES

    def close(a, b, what):
        a, b = np.asarray(a), np.asarray(b)
        assert np.abs(a - b).max() <= 1e-5 * max(np.abs(b).max(), 1e-3), what

    close(got, want, "out")
    close(d_got[1], d_want[1], "d_x")
    for key in ("router", "w1", "w3", "w2"):
        close(d_got[0][key], d_want[0][key], key)

    # The routed part alone, cut to its rung against all N*k rows.
    N, k = _ROUTES // 2, 2
    _, _, top_p, top_idx, _ = moe._routing(params, x, k, True, 2.5, scoring)
    key = top_idx.reshape(N, k)
    mine = (key >= 2) & (key < 4)
    sorted_key, order, inv = moe._sort_routes(jnp.where(mine, key - 2, 2))
    sizes = jnp.diff(jnp.searchsorted(sorted_key, jnp.arange(3))).astype(
        jnp.int32)
    floats = (x.reshape(N, -1), top_p.reshape(N, k), params["w1"],
              params["w3"], params["w2"])
    ints = (order, inv, mine, sizes)
    assert rungs[int(moe._held_ffn_fwd(rungs, jnp.float32, floats,
                                       ints)[1][2])] == rows

    def routed(rungs):
        return jax.value_and_grad(lambda floats: jnp.sum(w.reshape(N, -1) * (
            moe._held_ffn(rungs, jnp.float32, floats, ints))))(floats)

    with jax.default_matmul_precision("highest"):
        (cut, d_cut), (full, d_full) = routed(rungs), routed(rungs[-1:])
    close(cut, full, "routed part")
    for got, want, what in zip(d_cut, d_full, ("d_x", "d_top_p", "d_w1",
                                               "d_w3", "d_w2")):
        close(got, want, what)
    assert (np.asarray(d_cut[1])[~np.asarray(mine)] == 0).all()


def test_rows_beyond_the_held_routes_count_exactly_zero_in_a_cut_buffer():
    """``test_laguna.py::test_routes_held_elsewhere_count_exactly_zero...``
    for the first C rows: whatever the grouped matmul leaves in the rows
    between the held routes and C, a NaN too, reaches neither the output nor
    a cotangent."""
    N, C, live_rows, D = 6, 8, 5, 4
    rng = np.random.RandomState(0)
    tok = jnp.asarray(rng.randint(N, size=C), jnp.int32)
    live = jnp.arange(C) < live_rows
    clean = jnp.asarray(rng.randn(C, D), jnp.float32).at[live_rows:].set(0.0)
    dirty = clean.at[live_rows:].set(jnp.nan)
    weight = jnp.where(live, jnp.asarray(rng.rand(C), jnp.float32), 0)

    def combine(down):
        return jax.value_and_grad(
            lambda d, p: jnp.sum(moe._combine_first(
                d, p, tok, live, N, jnp.float32) ** 2), (0, 1))(down, weight)

    (out, (d_down, d_p)), (out0, (d_down0, d_p0)) = combine(dirty), combine(
        clean)
    assert np.isfinite(float(out)) and float(out) == float(out0)
    assert (np.asarray(d_down[live_rows:]) == 0).all()
    assert (np.asarray(d_down[:live_rows])
            == np.asarray(d_down0[:live_rows])).all()
    assert (np.asarray(d_p) == np.asarray(d_p0)).all()
    assert (np.asarray(d_p[live_rows:]) == 0).all()

    x = jnp.asarray(rng.randn(N, D), jnp.float32)
    _, pull = jax.vjp(lambda x: moe._dispatch_first(x, tok, live), x)
    d_x, d_x0 = pull(dirty)[0], pull(clean)[0]
    assert np.isfinite(np.asarray(d_x)).all()
    assert (np.asarray(d_x) == np.asarray(d_x0)).all()


def _route_rows_traced() -> float:
    return sum(v["value"] for name, v in metrics.snapshot().items()
               if name.startswith("moe.route_rows"))


def test_holding_every_expert_traces_no_branch_and_counts_no_rung():
    """``held=None`` is straight-line code (the one loop is
    ``jnp.searchsorted``'s, as it was); a share lowers to a ``case`` and
    counts its ladder once a trace."""
    params, x = _layer_routing_by_hand(24, "softmax")
    full = jax.tree_util.tree_map(
        jnp.asarray, init_moe_params(16, 8, 8, seed=0))

    def lowered(params, held):
        return jax.jit(jax.grad(lambda p, x: jnp.sum(moe_ffn(
            p, x, top_k=2, dispatch="grouped", held=held,
            aux=False)[0]))).lower(params, x).as_text()

    before = _route_rows_traced()
    text = lowered(full, None)
    assert "stablehlo.case" not in text and "conditional" not in text
    assert text.count("stablehlo.while") == 1
    assert _route_rows_traced() == before
    counter = metrics.counter("moe.route_rows", {"rungs": "2", "of": "128"})
    mine = counter.value
    assert "stablehlo.case" in lowered(params, _HELD)
    assert counter.value == mine + 1


def test_route_rungs_by_hand():
    laguna = route_rungs(81920, 16, 256)       # even share 5,120
    assert laguna == (10240, 81920)
    xing = route_rungs(32768, 8, 64)           # even share 4,096
    assert xing == (8192, 32768)
    for routes, count, experts in ((6, 1, 8), (128, 2, 8), (144, 3, 8),
                                   (40, 7, 8), (81920, 255, 256)):
        rungs = route_rungs(routes, count, experts)
        assert rungs[-1] == routes and all(
            a < b for a, b in zip(rungs, rungs[1:])), rungs


def test_route_rows_follows_the_steps_counted_routes():
    cfg = TransformerConfig(vocab_size=64, dim=32, n_layers=2, n_heads=4,
                            hidden=16, max_seq=32, num_experts=8,
                            experts_held=2, experts_first=2, top_k=2,
                            moe_dispatch="grouped", aux_loss_coef=0.0,
                            compute_dtype=jnp.float32)
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("dp",))
    tr = TransformerTrainer(cfg, mesh, updater_type="sgd")
    assert tr.route_rows() is None                 # no step yet
    tr.train_step(np.random.RandomState(1).randint(
        64, size=(2, 32)).astype(np.int32))
    routes = np.asarray(tr.routes)                 # [2 layers, 2 held + 1]
    assert routes.shape == (2, 3) and (routes.sum(axis=1) == 128).all()
    rungs = np.asarray(route_rungs(128, 2, 8))
    want = [rungs[rungs >= held].min() / 128
            for held in routes[:, :2].sum(axis=1)]
    assert tr.route_rows().tolist() == want
    # a batch routed wholly here walks every row; one routed wholly
    # elsewhere takes the lowest rung
    tr.routes = jnp.asarray([[100, 28, 0], [0, 0, 128]], jnp.int32)
    assert tr.route_rows().tolist() == [1.0, 64 / 128]
    dense = TransformerTrainer(replace(cfg, moe_dispatch="dense"), mesh)
    dense.routes = tr.routes
    assert dense.route_rows() is None
