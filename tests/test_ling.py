"""Ling-3.0-flash's decoder on the normal path (ISSUE 36): Kimi Delta
Attention layers through the chunked scan of ``ops/kda.py``, five to one
beside latent attention without a query latent and with a head-wise gate, a
router limited by groups over a share of the experts, held to the plain
reference ``benchmarks/reference/ling_lm.py``, small, on the CPU."""

import json
import math
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

from benchmarks import flops_ling  # noqa: E402
from benchmarks.reference import ling_lm  # noqa: E402
from multiverso_tpu import metrics  # noqa: E402
from multiverso_tpu.models import (TransformerConfig,  # noqa: E402
                                   TransformerTrainer, init_params)
from multiverso_tpu.models import moe  # noqa: E402
from multiverso_tpu.models.common import Ctx, Draw, rms_norm  # noqa: E402
from multiverso_tpu.models.transformer import (STACKED_RUN,  # noqa: E402
                                               LayerKind, _attn_sub,
                                               _init_layer,
                                               _loss_routes_loads,
                                               expert_load, lm_loss,
                                               transformer_forward)
from multiverso_tpu.ops import kda as kda_ops  # noqa: E402
from multiverso_tpu.updaters import AddOption  # noqa: E402

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CONFIG = os.path.join(REPO, "benchmarks", "configs",
                      "ling-3.0-flash-vl-l6.json")
CELL = "ling-3.0-flash-vl-l6.zipf-seq16k-b1"
LINEAR, LATENT = "linear_attention", "latent_attention"


def _model(**over) -> dict:
    """The six-layer cut at toy widths: a linear layer with a dense FFN, four
    linear layers and a latent one with routed FFNs that hold the first 4 of
    16 experts (a whole group of 4) under a sigmoid top-4 router that keeps 2
    of 4 groups."""
    model = dict(
        vocab_size=96, dim=32, n_layers=6, n_heads=2, head_dim=16, hidden=16,
        dense_hidden=48, shared_expert_hidden=16, max_seq=256, norm_eps=1e-6,
        layer_types=[LINEAR] * 5 + [LATENT],
        mlp_layer_types=["dense"] + ["sparse"] * 5, layer_period=6,
        attn_gate="per_head", q_lora_rank=0,
        kv_lora_rank=12, qk_nope_dim=8, qk_rope_dim=4, v_head_dim=8,
        rope_latent=dict(theta=6e6), linear_conv_kernel=4,
        kda_lower_bound=-5.0, num_experts=16, experts_held=4,
        experts_first=0, top_k=4, n_group=4, topk_group=2,
        norm_topk_prob=True, routed_scale=2.5, router_scoring="sigmoid",
        router_bias_rate=0.001, moe_dispatch="grouped", aux_loss_coef=0.0,
        router_z_loss_coef=0.0, scan_layers=True, remat=True,
        remat_policy="full")
    model.update(over)
    return model


def _cfg(model: dict) -> TransformerConfig:
    return TransformerConfig(compute_dtype=jnp.float32, **model)


def _tokens(seed: int, batch: int, seq: int, vocab: int = 96):
    return np.random.RandomState(seed).randint(0, vocab, (batch, seq)
                                               ).astype(np.int32)


def _moved(params, seed: int = 5):
    """``params`` with the leaves that rest at 1 or 0 moved off them, so
    that a gain or a bias that is dropped shows."""
    rng = np.random.RandomState(seed)

    def move(path, leaf):
        name = getattr(path[-1], "key", "")
        if name.endswith("norm") or name == "o_norm":
            return leaf * (1 + 0.2 * rng.randn(*leaf.shape)).astype(
                np.float32)
        if name == "router_bias":
            return leaf + (0.05 * rng.randn(*leaf.shape)).astype(np.float32)
        return leaf

    return jax.tree_util.tree_map_with_path(move, params)


# ------------------------------------------------------- (a) the scan itself
def _recurrence(q, k, v, g, beta):
    """The recurrence of ``ops/kda.py``'s docstring, token by token."""
    B, T, H, dk = q.shape

    def step(S, x):
        q, k, v, g, b = x
        S = S * jnp.exp(g)[..., None]
        u = b[..., None] * (v - jnp.einsum("bhkv,bhk->bhv", S, k))
        S = S + k[..., None] * u[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, q)

    xs = tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta))
    _, o = jax.lax.scan(step, jnp.zeros((B, H, dk, v.shape[-1]),
                                        jnp.float32), xs)
    return jnp.moveaxis(o, 0, 1)


def _scan_inputs(seed, B, T, H, dk, dv):
    """Unit keys and queries, and log-decays at both ends of (-5, 0)."""
    r = np.random.RandomState(seed)
    q, k = r.randn(2, B, T, H, dk)
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * dk ** 0.5
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    g = -5.0 / (1 + np.exp(-4 * r.randn(B, T, H, dk)))
    beta = 1 / (1 + np.exp(-r.randn(B, T, H)))
    return tuple(jnp.asarray(x, jnp.float32)
                 for x in (q, k, r.randn(B, T, H, dv), g, beta))


def _take(monkeypatch, path):
    """Make ``kda`` take ``path``: the kernels interpreted, or ``jax.numpy``."""
    if path == "interpret":
        monkeypatch.setenv("MVTPU_FORCE_FLASH", "1")
    else:
        monkeypatch.delenv("MVTPU_FORCE_FLASH", raising=False)


@pytest.mark.parametrize("path", ["jnp", "interpret"])
def test_kda_matches_the_recurrence_token_by_token(path, monkeypatch):
    """Outputs and every input's gradient, at a length that no sub-block
    divides (200 = 3 chunks + 8 tokens: padded), with decays from e^-5 to
    1 - 1e-6 in one chunk: the quotient trick's worst case."""
    _take(monkeypatch, path)
    args = _scan_inputs(0, 2, 200, 2, 32, 16)
    assert float(args[3].min()) < -4.99 and float(args[3].max()) > -1e-3
    weight = jnp.asarray(np.random.RandomState(1).randn(2, 200, 2, 16),
                         jnp.float32)
    got = kda_ops.kda(*args)
    want = _recurrence(*args)
    assert got.shape == want.shape
    assert float(jnp.max(jnp.abs(got - want))) < 2e-6
    d_got = jax.grad(lambda *a: jnp.sum(kda_ops.kda(*a) * weight),
                     argnums=range(5))(*args)
    d_want = jax.grad(lambda *a: jnp.sum(_recurrence(*a) * weight),
                      argnums=range(5))(*args)
    for name, a, b in zip(("q", "k", "v", "g", "beta"), d_got, d_want):
        rel = float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
        assert rel < 2e-5, (name, rel)


def test_kda_carries_the_state_across_chunks_and_refuses_wrong_shapes():
    """A token's output depends on tokens chunks back (so the carried state
    is what is tested), and a shape that is not the contract's is refused by
    name."""
    q, k, v, g, beta = _scan_inputs(3, 1, 192, 1, 16, 16)
    g = g * 0.01                                  # a long memory
    base = kda_ops.kda(q, k, v, g, beta)
    moved = kda_ops.kda(q, k, v.at[:, 3].add(1.0), g, beta)
    assert float(jnp.max(jnp.abs((moved - base)[:, 150:]))) > 1e-6
    assert float(jnp.max(jnp.abs((moved - base)[:, :3]))) == 0.0
    with pytest.raises(ValueError, match="kda wants"):
        kda_ops.kda(q, k, v, g, beta[..., None])


def _kda_grads(monkeypatch, path, args, d_o):
    """The five gradients of ``sum(kda(...) * d_o)`` on ``path``."""
    _take(monkeypatch, path)

    def grads(*args):                  # traced anew a call: the path's own
        o, pull = jax.vjp(kda_ops.kda, *args)
        return pull(d_o.astype(o.dtype))

    return jax.jit(grads)(*args)


@pytest.mark.parametrize("dtype,shape", [
    ("float32", (1, 64, 1, 16, 16)),             # one chunk, one head
    ("float32", (2, 200, 1, 16, 16)),            # three chunks + a padded tail
    ("float32", (1, 200, 2 * kda_ops._HEADS, 16, 16)),   # two grid groups
    ("float32", (1, 200, 2, 32, 16)),            # d_k != d_v
    ("bfloat16", (1, 64, 1, 16, 16)),
    ("bfloat16", (1, 200, 2 * kda_ops._HEADS, 16, 16)),
    ("bfloat16", (2, 200, 2, 32, 16)),
])
def test_the_kernel_backward_gives_the_jnp_paths_gradients(dtype, shape,
                                                           monkeypatch):
    """``kda_bwd`` (interpreted) against ``_scan_bwd`` + ``jax.vjp(_intra)``:
    in float32 the same arithmetic in another order; in bfloat16 the kernel
    also rounds a cotangent where it is a product's operand, as the TPU's
    default precision does to XLA's form (``g``'s gradient hangs on the
    most of them)."""
    q, k, v, g, beta = _scan_inputs(7, *shape)
    args = (*(x.astype(dtype) for x in (q, k, v)), g, beta)
    d_o = jnp.asarray(np.random.RandomState(8).randn(*v.shape), jnp.float32)
    got = _kda_grads(monkeypatch, "interpret", args, d_o)
    want = _kda_grads(monkeypatch, "jnp", args, d_o)
    for name, a, b in zip(("q", "k", "v", "g", "beta"), got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        rel = float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
        bound = 2e-5 if dtype == "float32" else (
            3e-2 if name == "g" else 8e-3)
        assert rel < bound, (name, rel)


def test_the_kernel_backward_carries_the_states_cotangent(monkeypatch):
    """A gradient at token 3 hangs on ``d_o`` at tokens 150-159, two chunks
    on (the ``d_s`` in VMEM is what is tested), and nothing after them has
    a gradient: the mirror of the forward's test above."""
    q, k, v, g, beta = _scan_inputs(3, 1, 192, 1, 16, 16)
    g = g * 0.01                                  # a long memory
    d_o = jnp.zeros(v.shape).at[:, 150:160].set(1.0)
    grads = _kda_grads(monkeypatch, "interpret", (q, k, v, g, beta), d_o)
    for name, d in zip(("q", "k", "v", "g", "beta"), grads):
        if name != "q":                           # q_t meets d_o_t alone
            assert float(jnp.max(jnp.abs(d[:, 3]))) > 1e-6, name
        assert float(jnp.max(jnp.abs(d[:, 160:]))) == 0.0, name
    assert float(jnp.max(jnp.abs(grads[0][:, :150]))) == 0.0


@pytest.mark.parametrize("path", ["jnp", "interpret"])
def test_kda_counts_its_traces(path, monkeypatch):
    """One ``attention.linear_traced`` a trace of ``kda`` and one
    ``attention.linear_bwd_traced`` a trace of its backward, under the
    labels the accepted runner reads, and one
    ``attention.linear_solve_traced{heads=,pairs=,lone=}`` a trace of the
    forward kernel."""
    _take(monkeypatch, path)
    labels = {"heads": "2", "chunk": str(kda_ops.CHUNK), "path": path}
    fwd = metrics.counter("attention.linear_traced", labels)
    bwd = metrics.counter("attention.linear_bwd_traced", labels)
    solve = metrics.counter("attention.linear_solve_traced",
                            {"heads": "2", "pairs": "1", "lone": "0"})
    before, solve_before = (fwd.value, bwd.value), solve.value
    args = _scan_inputs(0, 1, 64, 2, 16, 16)
    jax.eval_shape(lambda *a: kda_ops.kda(*a), *args)    # no cached trace
    assert (fwd.value, bwd.value) == (before[0] + 1, before[1])
    jax.eval_shape(jax.grad(lambda *a: jnp.sum(kda_ops.kda(*a)),
                            argnums=range(5)), *args)
    assert (fwd.value, bwd.value) == (before[0] + 2, before[1] + 1)
    for s in metrics.REGISTRY.series():
        if s.name in ("attention.linear_traced",
                      "attention.linear_bwd_traced"):
            assert sorted(s.labels) == ["chunk", "heads", "path"], s.labels
        if s.name == "attention.linear_solve_traced":
            assert sorted(s.labels) == ["heads", "lone", "pairs"], s.labels
    # the forward kernel's solves, one count a trace of the kernel (none on
    # the ``jnp`` path): two heads are one pair, three heads three lone ones
    assert solve.value == solve_before + (2 if path == "interpret" else 0)
    lone = metrics.counter("attention.linear_solve_traced",
                           {"heads": "3", "pairs": "0", "lone": "1"})
    lone_before = lone.value
    jax.eval_shape(lambda *a: kda_ops.kda(*a),
                   *_scan_inputs(0, 1, 64, 3, 16, 16))
    assert lone.value == lone_before + (path == "interpret")


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn
        for param in eqn.params.values():
            for sub in (param if isinstance(param, (list, tuple))
                        else [param]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


@pytest.mark.parametrize("name", ["kda_fwd", "kda_bwd"])
def test_the_kernels_hold_the_stated_precision(name):
    """Read off the kernel's own jaxpr on bfloat16 inputs: every exponential
    is float32 in and out and meets nothing but a float32 product (a decay
    rounded to bfloat16 would be a convert there, and no check of the
    numbers tells that apart: ``PERF.md`` section 7), a float32 product is
    at "highest" (the cumulative sums, the solve and its transpose), every
    other product has the inputs' dtype, the carried state or its cotangent
    is float32 VMEM, and ``dg`` and ``dbeta`` leave in float32."""
    q, k, v, g, beta = _scan_inputs(0, 1, 128, 2, 16, 16)
    args = (*(x.astype(jnp.bfloat16) for x in (q, k, v)), g, beta)
    traced = jax.make_jaxpr(lambda *a: jax.vjp(
        lambda *b: kda_ops._kda(*b, "interpret"), *a)[1](a[2]))(*args)
    (call,) = [e for e in _eqns(traced.jaxpr)
               if e.primitive.name == "pallas_call"
               and e.params["name"] == name]
    body = call.params["jaxpr"]
    eqns = list(_eqns(body))
    users = {}
    for eqn in eqns:
        for var in eqn.invars:
            if not hasattr(var, "val"):             # not a literal
                users.setdefault(var, []).append(eqn)
    exps = [e for e in eqns if e.primitive.name == "exp"]
    assert len(exps) >= 8
    for eqn in exps:
        assert eqn.invars[0].aval.dtype == jnp.float32
        assert {u.primitive.name for u in users[eqn.outvars[0]]} == {"mul"}
    dots = [e for e in eqns if e.primitive.name == "dot_general"]
    for eqn in dots:
        kinds = {var.aval.dtype for var in eqn.invars}
        assert eqn.outvars[0].aval.dtype == jnp.float32
        if kinds == {jnp.dtype(jnp.float32)}:
            assert "HIGHEST" in str(eqn.params["precision"])
        else:
            assert kinds == {jnp.dtype(jnp.bfloat16)}
    assert any(var.aval.dtype == jnp.float32 for e in dots
               for var in e.invars)
    carried = body.invars[-1].aval
    assert "vmem" in str(carried) and carried.dtype == jnp.float32
    if name == "kda_fwd":
        # two heads: a cumulative sum each and the ten products of ONE solve,
        # six of them SUB rows against the pair's 2 C x 2 C block diagonal
        wide = [e for e in dots if e.invars[0].aval.dtype == jnp.float32]
        C, S = kda_ops.CHUNK, kda_ops.SUB
        assert sorted(tuple(v.aval.shape for v in e.invars) for e in wide) \
            == sorted([((C, C), (C, 16))] * 2
                      + [((S, 2 * C), (2 * C, 2 * C))] * 6
                      + [((C, 2 * C), (2 * C, 2 * C))] * 4)
    if name == "kda_bwd":
        dq, dk, dv, dg, dbeta = call.outvars
        assert [x.aval.dtype for x in (dq, dk, dv)] == [jnp.bfloat16] * 3
        assert [x.aval.dtype for x in (dg, dbeta)] == [jnp.float32] * 2


def _solve_cases():
    """The all-ones lower triangle (all keys alike and beta 1) and three
    seeded random ``A``."""
    n = kda_ops.CHUNK
    rng = np.random.RandomState(0)
    return [jnp.tril(jnp.ones((n, n), jnp.float32), -1)] + [
        jnp.tril(jnp.asarray(rng.randn(n, n) * 0.3, jnp.float32), -1)
        for _ in range(3)]


def test_tri_inv_is_the_inverse_at_its_worst_case():
    """All keys alike and beta 1: ``I + A`` is the all-ones lower triangle,
    whose inverse a plain Neumann series loses to cancellation."""
    n = kda_ops.CHUNK
    a, *random = _solve_cases()
    t = kda_ops._tri_inv(a)
    assert float(jnp.max(jnp.abs(t @ (jnp.eye(n) + a) - jnp.eye(n)))) < 1e-5
    a = jnp.stack(random)
    t = kda_ops._tri_inv(a)
    want = np.linalg.inv(np.eye(n) + np.asarray(a, np.float64))
    assert float(np.max(np.abs(np.asarray(t) - want))) < 1e-3 * np.max(
        np.abs(want))


@pytest.mark.parametrize("first", range(4))
@pytest.mark.parametrize("count", [1, 2, 3, 4])
def test_the_packed_solve_is_the_plain_one(count, first):
    """``_tri_inv_packed`` over 1 to 4 matrices (a lone one, a pair, a pair
    and a lone one, two pairs), each case once at every place in its tile,
    against ``_tri_inv_impl`` with ``_mm32``: the packing adds exact zeros
    to the same sums, so two float32 ulps of the largest entry is room."""
    cases = _solve_cases()
    mats = [cases[(first + i) % len(cases)] for i in range(count)]
    got = kda_ops._tri_inv_packed(mats)
    assert len(got) == count
    for a, t in zip(mats, got):
        want = kda_ops._tri_inv_impl(a, kda_ops._mm32)
        assert t.shape == want.shape and t.dtype == jnp.float32
        top = float(jnp.max(jnp.abs(want)))
        assert float(jnp.max(jnp.abs(t - want))) <= 2 * np.spacing(
            np.float32(top))


@pytest.mark.parametrize("shape", [
    (1, 128, 1, 16, 16),                         # a lone head
    (1, 128, 2, 16, 16),                         # one pair
    (1, 128, 3, 16, 16),                         # three lone groups
    (1, 200, 2 * kda_ops._HEADS, 16, 16),        # two grid groups of pairs
    (2, 128, 2, 32, 16),                         # d_k != d_v
])
def test_the_forward_kernel_keeps_the_jnp_paths_solve_and_output(shape):
    """``kda_fwd`` interpreted against the ``jnp`` path in float32: its
    third output ``T`` against ``_tri_inv`` of the ``A`` that ``_intra``
    builds, its first against ``_fwd_jnp``'s ``o`` and its second against
    the states, whatever the heads a grid step pairs (the kernel builds
    ``A`` a sub-block at a time, so the two differ by roundings, not by
    heads)."""
    q, k, v, g, beta = _scan_inputs(11, *shape)
    pad = -shape[1] % kda_ops.CHUNK
    q, k, v, g, beta = (jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (
        x.ndim - 2)) for x in (q, k, v, g, beta))
    o, (states, solves) = kda_ops._fwd_kernel_call(q, k, v, g, beta, True)
    want_o, want_states = kda_ops._fwd_jnp(q, k, v, g, beta)
    assert float(jnp.max(jnp.abs(o - want_o))) < 1e-5
    # [n, B, H, ...] on the jnp path, [B, H, n, ...] from the kernel
    assert float(jnp.max(jnp.abs(
        jnp.moveaxis(states, 2, 0) - want_states))) < 5e-5 * max(
            1.0, float(jnp.max(jnp.abs(want_states))))
    n = q.shape[1] // kda_ops.CHUNK
    taken = []

    def spy(a):
        taken.append(kda_ops._tri_inv_impl(a, kda_ops._mm32))
        return taken[-1]

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(kda_ops, "_tri_inv", spy)
        kda_ops._intra(*(kda_ops._chunked(x, n) for x in (q, k, v, g, beta)))
    (want_t,) = taken                             # [n, B, H, C, C]
    assert solves.shape == (shape[0], shape[2], n, kda_ops.CHUNK,
                            kda_ops.CHUNK)
    got_t = jnp.moveaxis(solves, 2, 0)
    assert float(jnp.max(jnp.abs(got_t - want_t))) < 1e-5 * max(
        1.0, float(jnp.max(jnp.abs(want_t))))


# ----------------------------------------- (b) the whole small model, (e), (f)
def _reference_grads(params, tokens, model, layers):
    total, grads, bias_after, kept, _ = ling_lm.loss_and_grads(
        params, jnp.asarray(tokens), model, layers=layers)
    return float(total), grads, bias_after, kept


def _program_grads(params, tokens, cfg):
    (total, (routes, loads, kept)), grads = jax.value_and_grad(
        _loss_routes_loads, has_aux=True)(params, jnp.asarray(tokens), cfg,
                                          None)
    return float(total), grads, routes, loads, kept


def _close(got, want, what, rtol=2e-4):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    err = np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-12)
    assert err < rtol, (what, err)


def test_the_six_layer_model_matches_the_reference_loss_and_gradients():
    """1 dense + 4 linear-routed + 1 latent-routed, float32, every leaf of
    the three kinds of layer, the embedding and the final norm."""
    model = _model()
    cfg = _cfg(model)
    # seed 4: at seed 3 a token of this draw sits on a route's tie, and the
    # first layer's gradients part by 7e-4 (seeds 4 to 6 agree to 1e-5)
    params = _moved(init_params(cfg, seed=4))
    tokens = _tokens(0, 2, 100)              # 100: padded to two chunks
    want_loss, want, bias_after, kept = _reference_grads(
        params, tokens, model, layers=(0, 2, 5))
    got_loss, got, routes, loads, kept_got = _program_grads(params, tokens,
                                                            cfg)
    assert abs(got_loss - want_loss) < 2e-5 * abs(want_loss)
    _close(got["embed"], want["embed"], "embed")
    _close(got["out_norm"], want["out_norm"], "out_norm")
    for i in (0, 2, 5):
        mine = ling_lm.layer(got["layers"], i)
        for key, value in want["layers"][i].items():
            if key == "router_bias":         # moved by rule, no gradient
                assert float(jnp.max(jnp.abs(mine[key]))) == 0.0
                continue
            _close(mine[key], value, f"L{i}.{key}")
    assert {"conv_q", "conv_k", "conv_v", "A_log", "dt_bias", "wf", "wb",
            "o_norm", "wg"} <= set(want["layers"][2])
    assert "wq" in want["layers"][5] and "wq_a" not in want["layers"][5]
    # the step's counts: held routes and elsewhere; tokens that kept group 0
    assert routes.shape == (5, model["experts_held"] + 1)
    assert np.all(np.asarray(routes).sum(axis=1)
                  == tokens.size * model["top_k"])
    np.testing.assert_array_equal(
        np.asarray(kept_got), [int(kept[i]) for i in range(1, 6)])
    assert loads.shape == (5, model["num_experts"])


def test_a_bfloat16_state_or_decay_or_no_group_limit_is_another_result():
    """What the reference's switches change is far above what the program
    and the reference differ by (2e-5 of the loss, above)."""
    model = _model()
    params = _moved(init_params(_cfg(model), seed=3))
    tokens = jnp.asarray(_tokens(0, 2, 100))
    base = float(ling_lm.loss(params, tokens, model))
    for switch in (dict(state_dtype=jnp.bfloat16),
                   dict(decay_dtype=jnp.bfloat16), dict(group_limit=False)):
        other = float(ling_lm.loss(params, tokens, model, **switch))
        assert abs(other - base) > 1e-4 * abs(base), switch


class _Runtime:
    devices, seed = jax.devices()[:1], 11


def test_a_control_in_the_programs_place_comes_out_not_correct():
    """The runner's own check (``reference_check``: ``compare`` and
    ``scan_compare`` under the published widths' bounds) passes the program
    and refuses the reference with a switch thrown in the program's place
    (``controls``): a bfloat16 state, a decay left out, routing without the
    group limit."""
    from benchmarks.runners import lm_train_linear as runner

    model, lr = _model(), 0.01
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("dp",))
    trainer = TransformerTrainer(_cfg(model), mesh, updater_type="sgd",
                                 option=AddOption(learning_rate=lr), seed=1)
    tokens = _tokens(3, 1, 128)
    sound = runner.reference_check(trainer, ling_lm, model, tokens, lr,
                                   _Runtime)
    assert sound["ok"] and sound["scan"]["ok"], sound
    wrong = runner.controls(ling_lm, model, 128, _Runtime.seed,
                            whole=(trainer, tokens, _Runtime))
    assert set(wrong) == set(runner.CONTROLS)
    assert not wrong["state_bf16"]["model"]["ok"]
    assert not wrong["no_decay"]["model"]["ok"]
    assert wrong["no_decay"]["model"]["worst_decay"] == 1.0
    assert not wrong["no_group_limit"]["model"]["ok"]
    assert (wrong["no_group_limit"]["model"]["kept_mismatch"]
            > 5 * ling_lm.KEPT_MISMATCH)
    assert "scan" not in wrong["no_group_limit"]
    # the scan alone, at the published head width and half the check's length
    wide = dict(model, n_heads=4, head_dim=128)
    program = runner.scan_check(ling_lm, wide, 1024, 7)
    scans = runner.controls(ling_lm, wide, 1024, 7,
                            names=("state_bf16", "no_decay"))
    assert program["ok"], program
    assert not scans["state_bf16"]["scan"]["ok"], scans["state_bf16"]
    assert not scans["no_decay"]["scan"]["ok"]
    assert scans["no_decay"]["scan"]["grad_rel_err"]["da"] == 1.0
    assert (program["out_rel_err"] < ling_lm.SCAN_RTOL
            < scans["state_bf16"]["scan"]["out_rel_err"])


def test_the_trainer_steps_the_six_layer_model_and_moves_the_bias():
    model = _model()
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("dp",))
    trainer = TransformerTrainer(_cfg(model), mesh, seed=1)
    tokens = _tokens(2, 2, 128)
    before = trainer.loss(tokens)
    for _ in range(3):
        last = trainer.train_step_async(tokens)
    assert trainer.loss(tokens) < before and np.isfinite(float(last))
    assert trainer.routes.shape == (5, model["experts_held"] + 1)
    assert trainer.kept.shape == (5,)
    assert 0 < trainer.router_bias_absmax() <= 3.5 * model[
        "router_bias_rate"]
    assert trainer.route_rows().shape == (5,)
    load = expert_load(trainer.params, tokens, trainer.cfg)
    assert load.shape == (5, model["experts_held"] + 1)


def test_latent_attention_without_a_query_latent_and_with_the_head_gate():
    """(e) two latent layers, ``q = h wq`` and a sigmoid gate a head, against
    the reference; the gate moved off one half by a non-zero ``wg``."""
    model = _model(n_layers=2, layer_types=[LATENT] * 2,
                   mlp_layer_types=["dense"] * 2, num_experts=0,
                   experts_held=0, n_group=1, topk_group=1,
                   router_scoring="softmax", layer_period=0,
                   shared_expert_hidden=0)
    cfg = _cfg(model)
    params = _moved(init_params(cfg, seed=4))
    assert "wq" in params["layers"]and "wg" in params["layers"]
    tokens = _tokens(1, 2, 64)
    want_loss, want, _, _ = _reference_grads(params, tokens, model, (0, 1))
    got_loss, got, *_ = _program_grads(params, tokens, cfg)
    assert abs(got_loss - want_loss) < 2e-5 * abs(want_loss)
    for i in (0, 1):
        for key in ("wq", "wg", "wkv_a", "wkv_b", "wo", "kv_a_norm"):
            _close(got["layers"][key][i], want["layers"][i][key],
                   f"L{i}.{key}")
    ungated = dict(params, layers={k: (jnp.zeros_like(v) if k == "wg" else v)
                                   for k, v in params["layers"].items()})
    assert abs(float(lm_loss(ungated, jnp.asarray(tokens), cfg))
               - got_loss) > 1e-4


def _cut(run: int, **over) -> dict:
    """A dense-FFN linear layer, ``run`` linear-routed layers, a latent-routed
    one: one period."""
    return _model(n_layers=run + 2, layer_period=run + 2,
                  layer_types=[LINEAR] * (run + 1) + [LATENT],
                  mlp_layer_types=["dense"] + ["sparse"] * (run + 1), **over)


@pytest.mark.parametrize("run", [4, 3])
def test_layout_traces_one_body_for_the_four_alike_layers(run):
    """(f) the six-layer cut's period is a dense-FFN linear layer, a run of
    four linear-routed layers and a latent one: the run is held stacked and
    its block is traced once.  A run of three stays three slots, the tree a
    period of four slots always was (Laguna's, below)."""
    cfg = _cfg(_cut(run))
    lay, stacked = cfg.layout, run >= STACKED_RUN
    assert not lay.lead and len(lay.period) == run + 2 and lay.n_periods == 1
    assert lay.runs == (((0, 1), (1, run), (run + 1, 1)) if stacked
                        else tuple((s, 1) for s in range(run + 2)))
    period = init_params(cfg, seed=0)["layers"]["period"]
    assert len(period) == len(lay.runs)
    assert period[1]["wq"].shape[:2] == ((1, run) if stacked else (1, 32))
    # What a trace holds is what the compiler is handed: with the run
    # scanned, a deeper cut (two linear-routed layers more in the run) lowers
    # to as many matmuls, and slot by slot to more.
    def dots(model):
        cfg = _cfg(model)
        return jax.jit(lambda p, t: transformer_forward(p, t, cfg)).lower(
            init_params(cfg, seed=0), jnp.asarray(_tokens(0, 1, 64))
        ).as_text().count("stablehlo.dot_general")

    assert (dots(_cut(run)) == dots(_cut(run + 2))) == stacked


def test_a_stacked_run_and_a_list_of_layers_give_the_same_model():
    """The same six layers held as runs under the scan or as a list under
    the loop: one loss, and the bias rule moves the same biases."""
    tokens = _tokens(4, 2, 64)
    out = []
    for scan in (True, False):
        mesh = Mesh(np.asarray(jax.devices()[:1]), ("dp",))
        trainer = TransformerTrainer(_cfg(_model(scan_layers=scan)), mesh,
                                     seed=2)
        losses = [float(trainer.train_step_async(tokens)) for _ in range(2)]
        bias = [np.asarray(ling_lm.layer(trainer.params["layers"],
                                         i)["router_bias"])
                for i in range(1, 6)]
        out.append((losses, np.stack(bias), np.asarray(trainer.routes),
                    np.asarray(trainer.kept)))
    np.testing.assert_allclose(out[0][0], out[1][0], rtol=1e-6)
    for held_as_runs, as_a_list in zip(out[0][1:], out[1][1:]):
        np.testing.assert_array_equal(held_as_runs, as_a_list)


def test_a_period_of_four_slots_is_the_tree_the_accepted_runner_reads():
    """Three sliding layers and a full one a period (Laguna's): every slot an
    entry of its own, which is how ``lm_train_kinds._leaf`` and
    ``laguna_lm.layer``, files no PR but a ``benchmark`` one may edit, index
    the tree."""
    from benchmarks.reference import laguna_lm
    from benchmarks.runners import lm_train_kinds

    model = dict(
        vocab_size=96, dim=32, n_layers=9, n_heads=2, head_dim=16, hidden=16,
        dense_hidden=48, max_seq=128, n_kv_heads=1, sliding_window=8,
        layer_types=["full_attention"] + ["sliding_attention"] * 3
        + ["full_attention"] + ["sliding_attention"] * 3 + ["full_attention"],
        heads_per_layer=[2, 4, 4, 4, 2, 4, 4, 4, 2],
        mlp_layer_types=["dense"] + ["sparse"] * 8, layer_period=4,
        num_experts=8, top_k=2, moe_dispatch="grouped", scan_layers=True)
    cfg = _cfg(model)
    assert cfg.layout.runs == ((0, 1), (1, 1), (2, 1), (3, 1))
    layers = init_params(cfg, seed=0)["layers"]
    assert len(layers["period"]) == 4
    as_a_list = init_params(_cfg(dict(model, scan_layers=False)),
                            seed=0)["layers"]
    for i in range(9):
        np.testing.assert_array_equal(
            lm_train_kinds._leaf(layers, i, "wq"), as_a_list[i]["wq"])
        np.testing.assert_array_equal(
            laguna_lm.layer(layers, i)["wo"], as_a_list[i]["wo"])


# ------------------------------------------------ (c), (d) the group limit
def _moe_layer(seed=0, E=16, held=0, dim=24, hidden=12):
    params = moe.init_moe_params(dim, hidden, E, seed=seed, held=held,
                                 scoring="sigmoid")
    rng = np.random.RandomState(seed + 1)
    params["router"] = (0.5 * rng.randn(dim, E)).astype(np.float32)
    params["router_bias"] = (0.05 * rng.randn(E)).astype(np.float32)
    return {k: jnp.asarray(v) for k, v in params.items()}


@pytest.mark.parametrize("dispatch", ["grouped", "dense"])
def test_the_shares_parts_add_up_to_the_uncut_layer(dispatch):
    """(c) 4 groups of 4 experts, 2 groups kept: the parts that the four
    shares give (each a group's experts), the shared expert counted once, add
    up to the layer that holds all 16; and their counts of tokens that kept
    their group add up to tokens x topk_group."""
    whole = _moe_layer()
    x = jnp.asarray(np.random.RandomState(7).randn(2, 40, 24), jnp.float32)
    kw = dict(top_k=4, dispatch=dispatch, norm_topk_prob=True,
              routed_scale=2.5, aux=False, scoring="sigmoid",
              groups=(4, 2))
    full, _, _, load, _ = moe.moe_ffn(whole, x, **kw)
    assert load.shape == (16,) and int(load.sum()) == 80 * 4
    parts, kept = [], 0
    for first in range(0, 16, 4):
        share = dict(whole, **{k: whole[k][first:first + 4]
                               for k in ("w1", "w3", "w2")})
        out, _, _, counted, mine = moe.moe_ffn(share, x, held=(first, 4),
                                               **kw)
        assert counted.shape == (5,) and mine.shape == ()
        np.testing.assert_array_equal(counted[:4], load[first:first + 4])
        # a group that was not kept sends no route
        assert int(counted[:4].sum()) <= 4 * int(mine)
        parts.append(out)
        kept += int(mine)
    assert kept == 80 * 2
    np.testing.assert_allclose(sum(parts), full, rtol=2e-5, atol=2e-6)
    # without the limit the layer is another one
    free, *_, none = moe.moe_ffn(whole, x, **dict(kw, groups=(1, 1)))
    assert none is None
    assert float(jnp.max(jnp.abs(free - full))) > 1e-3


def test_the_group_limit_keeps_every_route_inside_the_kept_groups():
    params = _moe_layer(seed=3)
    x = jnp.asarray(np.random.RandomState(8).randn(1, 64, 24), jnp.float32)
    probs, _, top_p, top_idx, kept = moe._routing(
        params, x, 4, True, 2.5, "sigmoid", (4, 2))
    assert kept.shape == (1, 64, 4) and np.all(np.asarray(kept).sum(-1) == 2)
    assert np.all(np.take_along_axis(np.asarray(kept),
                                     np.asarray(top_idx) // 4, axis=-1))
    # the kept groups are those whose two best (score + bias) sum highest
    chosen = np.asarray(probs + params["router_bias"]).reshape(64, 4, 4)
    two = np.sort(chosen, axis=-1)[..., -2:].sum(-1)
    want = np.argsort(-two, axis=-1, kind="stable")[:, :2]
    assert all(set(np.flatnonzero(k)) == set(w)
               for k, w in zip(np.asarray(kept)[0], want))
    np.testing.assert_allclose(np.asarray(top_p).sum(-1), 2.5, rtol=1e-6)


@pytest.mark.parametrize("scoring", ["sigmoid", "softmax"])
def test_one_group_is_the_routing_it_was_bit_for_bit(scoring):
    """(d) ``groups=(1, 1)`` against the router written out as it stood, and
    every group kept against no limit."""
    params = _moe_layer(seed=5)
    x = jnp.asarray(np.random.RandomState(9).randn(2, 32, 24), jnp.float32)
    probs, logits, top_p, top_idx, kept = moe._routing(
        params, x, 4, True, 2.5, scoring)
    assert kept is None
    lg = x @ params["router"]
    if scoring == "sigmoid":
        p = jax.nn.sigmoid(lg)
        _, idx = jax.lax.top_k(p + params["router_bias"], 4)
        w = jnp.take_along_axis(p, idx, axis=-1)
    else:
        p = jax.nn.softmax(lg, axis=-1)
        w, idx = jax.lax.top_k(p, 4)
    w = w / jnp.sum(w, axis=-1, keepdims=True) * 2.5
    for got, want in ((logits, lg), (probs, p), (top_idx, idx), (top_p, w)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    _, _, all_p, all_idx, all_kept = moe._routing(
        params, x, 4, True, 2.5, scoring, (4, 4))
    assert bool(jnp.all(all_kept))
    np.testing.assert_array_equal(np.asarray(all_idx), np.asarray(top_idx))
    np.testing.assert_array_equal(np.asarray(all_p), np.asarray(top_p))


def test_one_group_traces_the_program_no_limit_traces():
    """A model whose ``n_group`` and ``topk_group`` are 1 lowers to the text
    it lowers to without the fields."""
    base = _model(n_group=1, topk_group=1)
    texts = []
    for model in (base, {k: v for k, v in base.items()
                         if k not in ("n_group", "topk_group")}):
        cfg = _cfg(model)
        params = init_params(cfg, seed=0)
        texts.append(jax.jit(lambda p, t, cfg=cfg: lm_loss(p, t, cfg)).lower(
            params, jnp.asarray(_tokens(0, 1, 64))).as_text())
    assert texts[0] == texts[1]


# ------------------------------------------------------------------ refusals
@pytest.mark.parametrize("over,match", [
    (dict(q_lora_rank=-1), "q_lora_rank >= 0"),
    (dict(kv_lora_rank=0), "latent_attention layers need"),
    (dict(n_kv_heads=1), "take no n_kv_heads or qk_norm"),
    (dict(qk_norm=True), "take no n_kv_heads or qk_norm"),
    (dict(kda_lower_bound=0.5), "kda_lower_bound < 0"),
    (dict(n_group=3), "must lie in n_group groups"),
    (dict(topk_group=5), "must lie in n_group groups"),
    (dict(num_experts=0, experts_held=0, n_group=1, topk_group=1,
          router_scoring="softmax"), "sparse layers need num_experts"),
])
def test_the_configuration_refuses_by_name(over, match):
    with pytest.raises(ValueError, match=match):
        _cfg(_model(**over))


@pytest.mark.parametrize("axes,shape,match", [
    (("dp", "sp"), (1, 2), "linear_attention does not run over sp=2"),
    (("dp", "tp"), (1, 2), "linear_attention does not shard over 'tp'"),
    (("dp",), (2,), "linear_attention runs on one device"),
])
def test_layouts_the_linear_kind_does_not_support_refuse_it(axes, shape,
                                                             match):
    model = _model(n_layers=2, layer_types=[LINEAR] * 2,
                   mlp_layer_types=["dense"] * 2, num_experts=0,
                   experts_held=0, n_group=1, topk_group=1,
                   router_scoring="softmax", layer_period=0,
                   shared_expert_hidden=0)
    devices = jax.devices()
    if len(devices) < int(np.prod(shape)):
        pytest.skip("needs the virtual devices of tests/conftest.py")
    mesh = Mesh(np.asarray(devices[:int(np.prod(shape))]).reshape(shape),
                axes)
    with pytest.raises(ValueError, match=match):
        trainer = TransformerTrainer(_cfg(model), mesh, seed=0)
        trainer.train_step_async(_tokens(0, 2, 64))


# ------------------------------------------------- the benchmark's own files
def test_the_configuration_file_holds_the_published_numbers():
    with open(CONFIG) as f:
        config = json.load(f)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    declared = {c["name"]: c for c in bench["configs"]}[config["name"]]
    assert declared["source"] == config["source"]
    assert set(declared["reduced"]) == set(config["reduced"])
    assert {"kda_safe_gate", "gate_granularity", "layer_pattern",
            "optimizer"} <= set(config["assumed"])
    if not os.path.isfile(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row = {r["source_url"]: r for r in map(json.loads, f)}[
            config["source"]]
    for key, value in row["config"].items():
        if key not in declared["reduced"]:
            assert config[key] == value, key
    model = config["model"]
    TransformerConfig(**model)                       # the program takes it
    assert model["experts_held"] == config["num_experts"] == 16
    assert model["num_experts"] == config["published"]["num_experts"] == 512


def test_the_step_and_the_scan_are_counted_by_hand():
    with open(CONFIG) as f:
        model = json.load(f)["model"]
    B, T = 1, 16384
    H, D = model["n_heads"], model["head_dim"]
    scan = flops_ling.kda_flops(model, B, T)
    # a head and token, forward: decay D^2, k^T S and the update 2 x 2 D^2,
    # S^T q 2 D^2; five linear layers; backward twice that
    assert scan["fwd"] == 5 * B * T * H * 7 * D * D
    assert scan["bwd"] == 2 * scan["fwd"]
    moved = flops_ling.kda_bytes(model, B, T)
    assert moved["bwd"] > moved["fwd"] > 5 * B * T * H * D * (4 * 2 + 4)
    kernel = flops_ling.mla_kernel_flops(model, B, T)
    pairs = B * H * T * (T + 1) // 2             # ONE latent layer
    assert kernel["fwd"] == (2 * 192 + 2 * 128) * pairs
    held = 1000.0
    total = flops_ling.train_flops(model, B, T, held)
    assert total > 6.0 * flops_ling.token_matmul_params(model) * B * T
    assert 420e6 < flops_ling.token_matmul_params(model) < 440e6


# ------------------------------------------- the sub-layer, flat (ISSUE 47)
def _plain_sub(cfg, heads, x, lyr):
    """The linear sub-layer in its 4-D form, as the layer was written before
    it kept the scan's layout: every ``[B, T, heads * head_dim]`` array
    reshaped to ``[B, T, heads, head_dim]`` for a head's reduction and a
    head's broadcast, flattened before ``wo``."""
    B, T, _ = x.shape
    D, taps, f32 = cfg.head_dim, cfg.linear_conv_kernel, jnp.float32

    def conv(y, kernel):
        padded = jnp.pad(y, ((0, 0), (taps - 1, 0), (0, 0)))
        y = sum(padded[:, j:j + T] * kernel[j] for j in range(taps))
        return jax.nn.silu(y).reshape(B, T, heads, D)

    def unit(y):
        return y * jax.lax.rsqrt(
            jnp.sum(jnp.square(y), axis=-1, keepdims=True) + 1e-6)

    h = rms_norm(x, lyr["attn_norm"], cfg.norm_eps)
    q = unit(conv(h @ lyr["wq"], lyr["conv_q"])) * D ** -0.5
    k = unit(conv(h @ lyr["wk"], lyr["conv_k"]))
    v = conv(h @ lyr["wv"], lyr["conv_v"])
    f = (h @ lyr["wf"] + lyr["dt_bias"]).reshape(B, T, heads, D)
    g = cfg.kda_lower_bound * jax.nn.sigmoid(
        jnp.exp(lyr["A_log"])[:, None] * f)
    o = rms_norm(kda_ops.kda(q, k, v, g, jax.nn.sigmoid(h @ lyr["wb"])),
                 lyr["o_norm"], cfg.norm_eps)
    if cfg.attn_gate:
        o = o * jax.nn.sigmoid(h @ lyr["wg"])[..., None]
    return x + o.reshape(B, T, heads * D) @ lyr["wo"]


@pytest.mark.parametrize("gate", [True, False], ids=["gate", "no-gate"])
@pytest.mark.parametrize("taps", [1, 4])
@pytest.mark.parametrize("heads", [2, 3])
def test_the_flat_sub_layer_is_the_4d_one(heads, taps, gate):
    """``attn.linear`` kept in ``[B, T, heads * head_dim]`` from the
    projections to ``wo`` (a head's sums and broadcasts as products with a
    0/1 matrix) gives the 4-D form's output and the gradients of every leaf
    and of ``x``, in float32; one trace counts once."""
    model = _model(n_layers=1, n_heads=heads, layer_types=[LINEAR],
                   mlp_layer_types=["dense"], layer_period=1,
                   linear_conv_kernel=taps,
                   attn_gate="per_head" if gate else "")
    cfg = _cfg(model)
    kind = LayerKind(LINEAR, heads, "dense")
    lyr = _moved({"lyr": _init_layer(cfg, kind,
                                     Draw(jax.random.key(heads)))})["lyr"]
    lyr = {name: jnp.asarray(leaf) for name, leaf in lyr.items()
           if name not in ("w1", "w2", "w3", "mlp_norm")}
    assert ("wg" in lyr) == gate
    B, T = 2, 96                                 # a chunk and a half
    x = jax.random.normal(jax.random.key(7), (B, T, cfg.dim), jnp.float32)
    ct = jax.random.normal(jax.random.key(8), (B, T, cfg.dim), jnp.float32)

    def both(sub):
        out, pull = jax.vjp(sub, x, lyr)
        return out, pull(ct)

    flat = metrics.counter("attention.linear_flat_traced",
                           {"heads": str(heads)})
    before = flat.value
    ctx = Ctx(cfg, None)
    out, (dx, dlyr) = jax.jit(lambda: both(
        lambda x, lyr: _attn_sub(ctx, kind, x, lyr)))()
    assert flat.value == before + 1
    want, (want_dx, want_dlyr) = jax.jit(lambda: both(
        lambda x, lyr: _plain_sub(cfg, heads, x, lyr)))()
    assert flat.value == before + 1

    def rel(got, ref):
        return float(jnp.max(jnp.abs(got - ref)) / jnp.max(jnp.abs(ref)))

    assert rel(out, want) < 1e-6
    assert rel(dx, want_dx) < 1e-5
    assert set(dlyr) == set(want_dlyr) == set(lyr)
    for name in lyr:
        # the decay's two leaves sum terms of both signs over every token
        # and channel, in another order: [2-1-no-gate] reads 2e-5 on A_log
        limit = 1e-4 if name in ("A_log", "dt_bias") else 1e-5
        assert rel(dlyr[name], want_dlyr[name]) < limit, name


# ------------------------------------------------------ the TPU's compiler
@pytest.fixture(scope="module")
def one_v5e():
    """A described v5e chip (nothing attached): the TPU's own compiler runs
    here.  Asked for inside the fixture alone: only one process may load
    the TPU's library at a time."""
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


def _compiled_for_v5e(monkeypatch, fn, *shapes):
    """``fn`` compiled for the described chip as the program's own TPU path
    (the kernels, not ``jax.numpy``), outside the compile cache (an entry
    written here cannot be read back without a chip)."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("MVTPU_NO_FLASH", raising=False)
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return jax.jit(fn).lower(*shapes).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()


def test_kda_compiles_for_v5e_at_the_cells_shape(one_v5e, monkeypatch):
    """Mosaic takes ``kda_fwd`` and ``kda_bwd`` at 1 x 16,384 x 32 heads of
    128 x 128 in bfloat16 (the packed solve's 16-row cuts and lane
    concatenations among them), and nothing of XLA's chunked backward is left
    beside them (no loop under the ``kda_bwd`` scope).  Nothing runs: no
    measurement."""
    def shaped(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_v5e)

    def grads(*a):
        return jax.grad(lambda *a: jnp.sum(kda_ops.kda(*a).astype(
            jnp.float32)), argnums=range(5))(*a)

    B, T, H, D = 1, 16384, 32, 128
    solved = metrics.counter("attention.linear_solve_traced",
                             {"heads": str(H), "pairs": "2", "lone": "0"})
    before = solved.value
    text = _compiled_for_v5e(
        monkeypatch, grads,
        shaped(B, T, H, D), shaped(B, T, H, D), shaped(B, T, H, D),
        shaped(B, T, H, D, dtype=jnp.float32),
        shaped(B, T, H, dtype=jnp.float32)).as_text()
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    for name in ("kda_fwd", "kda_bwd"):
        assert any(f"/{name}" in line for line in calls), (name, calls)
    assert solved.value == before + 1            # the cell's: two pairs a step
    assert " while(" not in text and "dynamic-update-slice" not in text


def test_the_linear_sub_layer_compiles_flat_for_v5e(one_v5e, monkeypatch):
    """One ``attn.linear`` sub-layer's replay and backward (``jax.vjp``) at
    the cell's 1 x 16,384 x 2560, compiled for v5e: no relayout copy of a
    ``[B, T, heads * head_dim]`` array is left (none of 64 MiB or more; the
    4-D form held six of 256 MiB and one of 128) and the compiler counts
    under 26 GB accessed (36.5 in the 4-D form).  Nothing runs: no
    measurement."""
    import re

    with open(CONFIG) as f:
        cfg = TransformerConfig(**json.load(f)["model"])
    kind = cfg.layout.kinds[0]
    assert kind.attn == LINEAR
    ctx = Ctx(cfg, None)

    def shaped(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_v5e)

    lyr = jax.eval_shape(
        lambda: _init_layer(cfg, kind, Draw(jax.random.key(0))))
    lyr = {name: shaped(leaf) for name, leaf in lyr.items()
           if name not in ("w1", "w2", "w3", "mlp_norm")}
    x = shaped(jax.ShapeDtypeStruct((1, 16384, cfg.dim), cfg.compute_dtype))

    def replay_and_backward(x, lyr, ct):
        return jax.vjp(lambda x, lyr: _attn_sub(ctx, kind, x, lyr),
                       x, lyr)[1](ct)

    compiled = _compiled_for_v5e(monkeypatch, replay_and_backward, x, lyr, x)
    text = compiled.as_text()
    assert "/kda_fwd" in text and "/kda_bwd" in text
    width = {"f32": 4, "s32": 4, "u32": 4, "bf16": 2, "f16": 2, "s8": 1,
             "u8": 1, "pred": 1}
    copies = []
    for dtype, dims in re.findall(
            r"= (\w+)\[([\d,]*)\][^ ]* copy\(", text):
        copies.append((width[dtype] * math.prod(
            int(d) for d in dims.split(",") if d), f"{dtype}[{dims}]"))
    assert copies and max(copies)[0] < 64 * 2 ** 20, sorted(copies)[-4:]
    assert compiled.cost_analysis()["bytes accessed"] < 26e9


# ------------------------------------------------------ the cell, rehearsed
def test_the_cell_rehearses_at_toy_widths(tmp_path, monkeypatch):
    """The real runner, generator, reference and readers on the cell's own
    files shrunk to toy widths, on the CPU with the kernels interpreted:
    every check holds as on the chip, and the counters' metrics are read."""
    import time

    from benchmarks import harness

    monkeypatch.setenv("MVTPU_FORCE_FLASH", "1")
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["paths"] = ["tinybench"]
    with open(CONFIG) as f:
        config = json.load(f)
    config.update(hidden_size=64, num_attention_heads=2,
                  num_key_value_heads=2, head_dim=32, intermediate_size=96,
                  moe_intermediate_size=32,
                  moe_shared_expert_intermediate_size=32, kv_lora_rank=16,
                  qk_nope_head_dim=16, qk_rope_head_dim=8, rotary_dim=8,
                  v_head_dim=16, num_experts=4, vocab_size=256,
                  max_position_embeddings=256)
    config["published"]["router_width"] = 32
    config["model"].update(
        dim=64, n_heads=2, head_dim=32, dense_hidden=96, hidden=32,
        shared_expert_hidden=32, kv_lora_rank=16, qk_nope_dim=16,
        qk_rope_dim=8, v_head_dim=16, num_experts=32, experts_held=4,
        vocab_size=256, max_seq=256)
    config["trainer"].update(learning_rate=0.005)
    for declared in bench["configs"]:
        if declared["name"] == config["name"]:
            declared["file"] = "tinybench/configs/ling.json"
    with open(os.path.join(REPO, "benchmarks", "traffic",
                           "zipf-seq16k-b1.json")) as f:
        traffic = json.load(f)
    traffic.update(batch=1, seq=256, check={"batch": 1, "seq": 128},
                   trace_seconds=0.5)
    for path, obj in (("tinybench/configs/ling.json", config),
                      ("tinybench/traffic/zipf-seq16k-b1.json", traffic),
                      ("BENCHMARK.json", bench)):
        os.makedirs(os.path.dirname(tmp_path / path), exist_ok=True)
        with open(tmp_path / path, "w") as f:
            json.dump(obj, f)
    cell = harness.load_cell(CELL, root=str(tmp_path))
    assert cell.runner == "lm_train_linear" and cell.chips == 1
    # the bounds are the published widths'; the toy's own are wider
    reference = harness.load_module(cell.search, "reference", "ling_lm")
    for bound, toy in (("GRAD_RTOL", 1.5), ("GRAD_RTOL_ROUTED", 1.5),
                       ("GRAD_RTOL_DECAY", 1.5), ("LOSS_ATOL", 0.05),
                       ("LOGITS_RTOL", 0.5), ("BIAS_MISMATCH", 0.5),
                       ("KEPT_MISMATCH", 0.2), ("SCAN_RTOL", 0.05),
                       ("SCAN_GRAD_RTOL", 0.1)):
        monkeypatch.setattr(reference, bound, toy)
    logged = []
    monkeypatch.setattr(harness.Runtime, "log",
                        lambda self, **fields: logged.append(fields))
    result = harness.run_cell(cell, seed=3000000011, seconds=0.5, trace=True,
                              t_start=time.perf_counter(), rehearsal=True,
                              out_root=str(tmp_path))
    check, = [f["reference_check"] for f in logged if "reference_check" in f]
    assert result["correct"], (
        [f for f in logged if "failed_checks" in f or "repeated_batch_losses"
         in f], sorted((k, v) for k, v in check.items()
                       if k not in ("grad_rel_err", "logits_rel_err")))
    assert result["failed"] == 0 and result["attempted"] >= 2
    assert 10.0 < result["metrics"]["model.moe_group_kept_share"][
        "value"] < 90.0                         # 50 under an even choice
    assert 1.0 < result["metrics"]["model.moe_held_route_share"][
        "value"] < 50.0                         # 12.5 under even routing
    runner = harness.load_module(cell.search, "runners", "lm_train_linear")
    assert check["layers"] == [0, 2, 5] and len(check["logits_rel_err"]) == 16
    classes = {c: sorted(k for k in check["grad_rel_err"]
                         if runner.leaf_class(k) == c)
               for c in ("decay", "routed")}
    assert classes["decay"] == ["L0.A_log", "L0.dt_bias", "L0.wf",
                                "L2.A_log", "L2.dt_bias", "L2.wf"]
    assert classes["routed"] == ["L2.router", "L2.w2", "L5.router", "L5.w2"]
    assert {"L0.conv_q", "L2.conv_k", "L2.conv_v", "L2.wb", "L2.o_norm",
            "L5.wq", "L5.wkv_a", "L5.wg", "L2.shared_w2", "L0.w2",
            "embed"} <= set(check["grad_rel_err"])
    assert set(check["kept_program"]) == {"1", "2", "3", "4", "5"} or set(
        check["kept_program"]) == {1, 2, 3, 4, 5}
    traced, = [f["attention_traced"] for f in logged
               if "attention_traced" in f]
    assert traced["jnp"] == 0 and traced["linear_jnp"] == 0
    assert traced["linear_interpret"] >= 1 and check["scan"]["ok"]
    assert traced["latent"] >= 1 and traced["groups"] >= 1
    held, = [f["held_routes"] for f in logged if "held_routes" in f]
    assert held["of"] == 5 * 256 * 8 and len(held["per_layer"]) == 5


# ------------------------------------------------- the readers of the trace
def _fake_trace():
    """Two steps' worth of device events on one chip, by hand: names as the
    compiled step gives them (``op_name``s of the sandbox's v5e compile)."""
    from benchmarks.trace import reduce as R

    ms = 1e6
    ops, op_names, t = [], {}, 0.0
    for name, op_name, dur in (
            ("kda_fwd.1", "jit(step)/jvp(layers)/while/body/closed_call/attn/"
             "attn.linear/kda_fwd/kda_fwd/pallas_call", 4),
            ("fusion.7", "jit(step)/jvp(layers)/while/body/closed_call/attn/"
             "attn.linear/dot_general", 10),
            ("kda_fwd.2", "jit(step)/transpose(jvp(layers))/while/body/"
             "closed_call/checkpoint/rematted_computation/attn/attn.linear/"
             "kda_fwd/kda_fwd/pallas_call", 4),
            ("fusion.9", "jit(step)/transpose(jvp(layers))/while/body/"
             "closed_call/checkpoint/attn/attn.linear/kda_bwd/while/body/"
             "closed_call/kda_bwd/jvp()/dot_general", 12),
            ("fusion.11", "kda_bwd/transpose(jvp())/reduce_sum", 2),
            ("flash_mla_fwd.3", "jit(step)/jvp(layers)/while/body/"
             "closed_call/attn/attn.latent/flash_mla_fwd/pallas_call", 6),
            ("fusion.13", "jit(step)/jvp(layers)/while/body/closed_call/mlp/"
             "moe.experts/mul", 8)):
        for step in range(2):
            start = (t + step * 100) * ms
            ops.append(R.Event(name, start, start + dur * ms))
        op_names[name] = op_name
        t += dur
    device = R.DeviceLines(ops=ops, modules=[
        R.Event("jit_step(1)", s * 100 * ms, (s * 100 + 60) * ms)
        for s in range(2)])
    trace = R.Trace(devices={"/device:TPU:0": device},
                    host=[R.Event(R.WINDOW_SPAN, 0.0, 200 * ms)])

    class Index:
        def op_name(self, event_name):
            return op_names.get(event_name)

    return trace, Index()


def test_the_trace_walk_books_the_scope_and_both_passes():
    from benchmarks.trace import linear

    found = linear.summarize(*_fake_trace())
    assert found.step_programs == 2
    # all time under a pass, kernel or fusion, with or without outer scopes
    assert found.by_pass_s == pytest.approx({"kda_fwd": 2 * 8e-3,
                                             "kda_bwd": 2 * 14e-3})
    assert found.scope_s == pytest.approx(2 * 32e-3)    # not latent, not mlp
    assert linear._pass_of("a/kda_bwd_dq/pallas_call") == "kda_bwd"
    assert linear._pass_of("a/attn.linear/mul") is None


def test_the_readers_read_the_facts_and_leave_out_what_is_not_there(
        monkeypatch):
    from benchmarks import harness
    from benchmarks.trace import linear

    with open(CONFIG) as f:
        model = json.load(f)["model"]
    found = linear.summarize(*_fake_trace())
    monkeypatch.setattr(linear, "of_reading", lambda reading: found)
    peaks = {"bf16_flops_per_s": 1.97e14, "hbm_bytes_per_s": 8.19e11}
    facts = {"chips": 1,
             "kda_flops_per_step": flops_ling.kda_flops(model, 1, 16384),
             "kda_bytes_per_step": flops_ling.kda_bytes(model, 1, 16384),
             "group_kept_per_step": 5 * 8000.0,
             "group_tokens_per_step": 5 * 16384}
    reading = harness.Reading(facts=facts, trace=object(), peaks=peaks,
                              compiles_in_window=0)
    readers = harness.layer_readers((harness.HERE,))
    assert readers["model.attn_linear_ms_per_step"].read(
        reading) == pytest.approx(32.0)
    # five layers' recurrence as written is memory-bound on a v5e: its bytes
    # at the HBM peak over the 8 ms a step under kda_fwd
    moved = facts["kda_bytes_per_step"]["fwd"]
    assert moved / 8.19e11 > facts["kda_flops_per_step"]["fwd"] / 1.97e14
    assert readers["kernel.kda_fwd_roofline"].read(reading) == pytest.approx(
        100 * moved / 8.19e11 / 8e-3)
    assert 0 < readers["kernel.kda_bwd_roofline"].read(reading) < 100
    assert readers["model.moe_group_kept_share"].read(
        reading) == pytest.approx(100 * 8000 / 16384)
    # a program without the scopes, or a run without a trace: left out
    monkeypatch.setattr(linear, "of_reading", lambda reading: None)
    for name in ("model.attn_linear_ms_per_step", "kernel.kda_fwd_roofline",
                 "kernel.kda_bwd_roofline"):
        assert readers[name].read(reading) is None
    bare = harness.Reading(facts={}, trace=None, peaks={},
                           compiles_in_window=0)
    assert readers["model.moe_group_kept_share"].read(bare) is None
    for name in ("model.attn_linear_ms_per_step", "kernel.kda_fwd_roofline",
                 "kernel.kda_bwd_roofline", "model.moe_group_kept_share"):
        r = readers[name]
        assert r.APPLIES == {"runner": "lm_train_linear"}
        assert r.MOVES == "tokens_per_chip_s"


def test_the_benchmark_declares_the_cell_and_its_metrics():
    from benchmarks import harness

    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = harness.load_cell(CELL)
    assert cell.chips == 1 and cell.runner == "lm_train_linear"
    assert cell.traffic["seq"] == 16384 and cell.traffic["batch"] == 1
    assert cell.traffic["check"] == {"batch": 1, "seq": 2048}
    names = {m["name"] for m in cell.per_layer}
    assert {"model.attn_linear_ms_per_step", "kernel.kda_fwd_roofline",
            "kernel.kda_bwd_roofline", "model.moe_group_kept_share",
            "kernel.flash_mla_fwd_roofline", "kernel.flash_mla_bwd_roofline",
            "model.attn_latent_ms_per_step",
            "model.moe_held_route_share", "kernel.moe_gmm_held_roofline",
            "model.mfu_pct", "device.idle_share"} <= names
    assert not names & {"kernel.flash_fwd_roofline", "kernel.flash_share",
                        "kernel.flash_roofline", "kernel.moe_gmm_roofline",
                        "model.hc_ms_per_step", "model.mtp_ms_per_step",
                        "kernel.flash_win_fwd_roofline",
                        "kernel.flash_mla_dq_roofline",
                        "kernel.flash_mla_dkv_roofline"}
    assert {m["name"] for m in cell.end_to_end} == {
        "setup_s", "tokens_per_chip_s", "peak_hbm_gib"}
    readers = harness.layer_readers(cell.search)
    assert names <= set(readers)
    assert bench["workloads"][7]["name"] == CELL   # later PRs' cells follow
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    for w in bench["workloads"] + bench["configs"]:
        assert len(w["why"]) <= 200
