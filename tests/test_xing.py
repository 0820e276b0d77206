"""Xing4.0-29B-A4B on the normal path (ISSUE 32): latent attention through a
flash kernel of two score widths, hyper-connection streams, sigmoid
bias-corrected routing over a share of the experts and a multi-token
prediction module, held to the plain reference
``benchmarks/reference/xing_lm.py``, small, on the CPU."""

import hashlib
import importlib
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

from benchmarks import flops_xing  # noqa: E402
from benchmarks.reference import xing_lm  # noqa: E402
from multiverso_tpu import metrics  # noqa: E402
from multiverso_tpu.models import (TransformerConfig,  # noqa: E402
                                   TransformerTrainer, init_params)
from multiverso_tpu.models.common import Draw  # noqa: E402
from multiverso_tpu.models.moe import (init_moe_params, moe_ffn,  # noqa: E402
                                       shared_expert)
from multiverso_tpu.models.transformer import (_bias_rule,  # noqa: E402
                                               _hc_gates, _hc_init,
                                               _loss_routes_loads,
                                               expert_load, lm_loss,
                                               param_shardings,
                                               transformer_forward)
from multiverso_tpu.ops.flash_attention import (  # noqa: E402
    fit_block, flash_attention_latent)
from multiverso_tpu.parallel.ring_attention import (  # noqa: E402
    blockwise_attention_local)

fa = importlib.import_module("multiverso_tpu.ops.flash_attention")

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CONFIG = os.path.join(REPO, "benchmarks", "configs",
                      "xing4.0-29b-a4b-e8.json")
CELL = "xing4.0-29b-a4b-e8.zipf-seq8k-b1"
LATENT = "latent_attention"


def _model(n_layers: int = 2, **over) -> dict:
    """Xing's block at toy widths: a leading dense layer, routed layers that
    hold 2 of 8 experts (the second share) under a sigmoid top-3 router, 4
    streams (3 Sinkhorn iterations unless a test says otherwise), one
    prediction module."""
    model = dict(
        vocab_size=96, dim=32, n_layers=n_layers, n_heads=4, hidden=16,
        dense_hidden=48, shared_expert_hidden=16, max_seq=64, norm_eps=1e-6,
        layer_types=[LATENT] * n_layers,
        mlp_layer_types=["dense"] + ["sparse"] * (n_layers - 1),
        q_lora_rank=12, kv_lora_rank=10, qk_nope_dim=8, qk_rope_dim=4,
        v_head_dim=8, attn_mscale=1.2,
        rope_latent=dict(theta=1e4, yarn_factor=4.0, original_max_seq=16),
        num_experts=8, experts_held=2, experts_first=2, top_k=3,
        norm_topk_prob=True, routed_scale=2.0, router_scoring="sigmoid",
        router_bias_rate=0.001, moe_dispatch="grouped", aux_loss_coef=0.0,
        router_z_loss_coef=0.0, hc_mult=4, hc_sinkhorn_iters=3,
        mtp_layers=1, mtp_loss_coef=0.3, scan_layers=True, remat=True,
        remat_policy="full")
    model.update(over)
    return model


def _tokens(vocab: int = 96, batch: int = 2, seq: int = 32, seed: int = 0):
    return jnp.asarray(np.random.RandomState(seed).randint(
        vocab, size=(batch, seq)).astype(np.int32))


def _rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def _stirred(params, seed: int = 5):
    """``init_params`` leaves the gates at their resting values and the bias
    at zero; moved off them so that every leaf matters to the loss."""
    rng = np.random.RandomState(seed)

    def stir(path, a):
        key = getattr(path[-1], "key", None)
        if key == "router_bias":
            return (0.05 * rng.randn(*a.shape)).astype(np.float32)
        if key == "alpha":
            return (a + 0.5).astype(np.float32)
        if key == "phi":
            return (a * 50).astype(np.float32)
        return a

    return jax.tree_util.tree_map(
        jnp.asarray, jax.tree_util.tree_map_with_path(stir, params))


def _leaves(tree):
    return {jax.tree_util.keystr(path): leaf for path, leaf
            in jax.tree_util.tree_flatten_with_path(tree)[0]}


# ------------------------------------------------- system against reference
@pytest.fixture(scope="module")
def wanted():
    """The reference's loss, gradients and biases after the step, once: the
    eight schedules below hold the same numbers in other trees."""
    model = _model(3, scan_layers=False)
    cfg = TransformerConfig(**model, compute_dtype=jnp.float32)
    params = _stirred(init_params(cfg, seed=1))
    return xing_lm.loss_and_grads(params, _tokens(), model,
                                  layers=(0, 1, 2))


@pytest.mark.parametrize("policy", ["full", "dots"])
@pytest.mark.parametrize("scan", [True, False], ids=["scan", "loop"])
@pytest.mark.parametrize("dispatch", ["grouped", "dense"])
def test_system_matches_the_reference_in_float32(wanted, dispatch, scan,
                                                 policy):
    """Loss, every leaf's gradient of every block, the bias after a step."""
    model = _model(3, moe_dispatch=dispatch, scan_layers=scan,
                   remat_policy=policy)
    cfg = TransformerConfig(**model, compute_dtype=jnp.float32)
    lay = cfg.layout
    assert (len(lay.lead), len(lay.period), lay.n_periods) == (1, 1, 2)
    params = _stirred(init_params(cfg, seed=1))
    tokens = _tokens()
    with jax.default_matmul_precision("highest"):
        (loss, (routes, loads, _)), grads = jax.jit(
            jax.value_and_grad(_loss_routes_loads, has_aux=True),
            static_argnums=(2, 3))(params, tokens, cfg, None)
        after = _bias_rule(cfg, params, loads)
    want_loss, want, want_bias = wanted
    assert abs(float(loss) - float(want_loss)) < 1e-5
    for i in range(3):
        got = _leaves(xing_lm.layer(grads["layers"], i))
        ref = _leaves(want["layers"][i])
        assert set(got) == set(ref)
        for key in got:
            if key.endswith("['router_bias']"):     # a rule's, no gradient's
                assert not np.asarray(got[key]).any()
            else:
                assert _rel(got[key], ref[key]) < 1e-4, (i, key)
    got, ref = _leaves(grads["mtp"]), _leaves(want["mtp"])
    assert set(got) == set(ref)
    for key in got:
        if not key.endswith("['router_bias']"):
            assert _rel(got[key], ref[key]) < 1e-4, key
    for key in ("embed", "out_norm"):
        assert _rel(grads[key], want[key]) < 1e-4, key
    # the rule: every routed block's bias, the module's last
    for i in (1, 2):
        assert np.array_equal(
            np.asarray(xing_lm.layer(after["layers"], i)["router_bias"]),
            np.asarray(want_bias[i]))
    assert np.array_equal(np.asarray(after["mtp"]["layer"]["router_bias"]),
                          np.asarray(want_bias["mtp"]))
    # the step's counted routes: [routed blocks, held + 1] and all experts'
    assert routes.shape == (3, 3) and loads.shape == (3, 8)
    assert (np.asarray(routes).sum(axis=1) == tokens.size * 3).all()
    assert np.array_equal(np.asarray(routes)[:, :2],
                          np.asarray(loads)[:, 2:4])
    assert np.array_equal(np.asarray(expert_load(params, tokens, cfg)),
                          np.asarray(routes))


def test_bfloat16_system_stays_near_the_reference():
    """The cell's precision at toy widths, where a rounding moves more than
    at 3584: the loss stays close and no leaf is off by its own size."""
    model = _model(3, hc_sinkhorn_iters=20)
    cfg = TransformerConfig(**model)
    params = jax.tree_util.tree_map(jnp.asarray, init_params(cfg, seed=2))
    tokens = _tokens(seed=3)
    (loss, _), grads = jax.jit(
        jax.value_and_grad(_loss_routes_loads, has_aux=True),
        static_argnums=(2, 3))(params, tokens, cfg, None)
    want_loss, want, _ = xing_lm.loss_and_grads(params, tokens, model,
                                                layers=(0, 1, 2))
    assert abs(float(loss) - float(want_loss)) < 0.05
    for i in range(3):
        got = _leaves(xing_lm.layer(grads["layers"], i))
        for key, ref in _leaves(want["layers"][i]).items():
            if key.endswith(("['wq_b']", "['wo']", "['w2']", "['phi']")):
                assert _rel(got[key], ref) < 1.0, (i, key)
    assert _rel(grads["embed"], want["embed"]) < 0.5


@pytest.mark.parametrize("switch,least", [
    (dict(rotated_part=False), 1e-3), (dict(sinkhorn_iters=0), 1e-2),
    (dict(bias=False), 1e-3), (dict(mtp_loss=False), 1e-1),
    (dict(gates_dtype=jnp.bfloat16), 1e-4)],
    ids=["rotated-part", "sinkhorn", "bias", "second-loss", "bf16-gates"])
def test_leaving_a_part_out_fails_the_comparison(switch, least):
    """What the check on the chip would refuse: the reference with one part
    switched off (or its gates and Sinkhorn in bfloat16, which the chip's
    check cannot tell from router flips: ``lm_train_latent.gate_scalar``) is
    another model, by far more than the float32 agreement (1e-5 on the loss,
    1e-4 a leaf) the eight cases above hold."""
    model = _model(3, scan_layers=False)
    cfg = TransformerConfig(**model, compute_dtype=jnp.float32)
    params = _stirred(init_params(cfg, seed=1))
    tokens = _tokens()
    whole = float(xing_lm.loss(params, tokens, model))
    assert abs(float(xing_lm.loss(params, tokens, model, **switch))
               - whole) > least


# ------------------------------------------------------ the two-width kernel
def _dense_latent_lse(qn, qr, kn, kr, v, scale):
    """Dense two-width causal attention and the rows' logsumexp beside it."""
    T = qn.shape[2]
    s = (jnp.einsum("bhtd,bhsd->bhts", qn, kn)
         + jnp.einsum("bhtd,bsd->bhts", qr, kr[:, 0])) * scale
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    return (jnp.einsum("bhts,bhsd->bhtd", jax.nn.softmax(s, axis=-1), v),
            jax.scipy.special.logsumexp(s, axis=-1))


def _dense_latent(qn, qr, kn, kr, v, scale):
    return _dense_latent_lse(qn, qr, kn, kr, v, scale)[0]


@pytest.mark.parametrize("T,heads,dn,dr,dv,bq,bk,bqb,bkb", [
    (96, 3, 16, 8, 16, 64, 64, 64, 64),       # blocks shrink to 32
    (128, 2, 16, 8, 8, 32, 64, 64, 32),
    (80, 1, 8, 8, 16, 16, 16, 16, 16)],
    ids=["T96-fit32", "T128-mixed", "T80-16"])
def test_flash_mla_kernels_against_dense_attention(T, heads, dn, dr, dv, bq,
                                                   bk, bqb, bkb):
    """Forward and all five gradients in interpret mode, T no multiple of
    the blocks asked for; the rotated key's gradient is one head's."""
    rng = np.random.RandomState(T)
    B = 2

    def draw(*shape):
        return jnp.asarray(rng.randn(*shape).astype(np.float32))

    args = (draw(B, heads, T, dn), draw(B, heads, T, dr),
            draw(B, heads, T, dn), draw(B, 1, T, dr), draw(B, heads, T, dv))
    weight = draw(B, heads, T, dv)
    scale = 0.3

    def flash(*a):
        return flash_attention_latent(*a, scale=scale, block_q=bq,
                                      block_k=bk, block_q_bwd=bqb,
                                      block_k_bwd=bkb, interpret=True)

    def dense(*a):
        return _dense_latent(*a, scale)

    with jax.default_matmul_precision("highest"):
        assert _rel(flash(*args), dense(*args)) < 1e-5
        got = jax.grad(lambda *a: jnp.sum(flash(*a) * weight),
                       argnums=range(5))(*args)
        want = jax.grad(lambda *a: jnp.sum(dense(*a) * weight),
                        argnums=range(5))(*args)
    for g, w, a in zip(got, want, args):
        assert g.shape == a.shape and _rel(g, w) < 1e-5


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


@pytest.mark.parametrize("seq", [8192, 16384], ids=["xing-8k", "ling-16k"])
def test_flash_mla_kernels_compile_for_v5e_at_the_cells_shape(one_v5e, seq):
    """Mosaic takes the forward and the ONE backward call at 1 x 32 x 8192
    (Xing's cell) and 1 x 32 x 16384 (Ling's), scores 128 + 64, values 128,
    at the blocks the dispatcher gives (512 x 1024 forward, 1024 x 1024
    backward) and inside the VMEM limit the backward asks for; the dq and dkv
    kernels are not in the program.  Nothing runs: no measurement."""
    from jax.experimental.compilation_cache import compilation_cache

    def shaped(heads, width):
        return jax.ShapeDtypeStruct((1, heads, seq, width), jnp.bfloat16,
                                    sharding=one_v5e)

    def grads(*a):
        return jax.grad(lambda *a: jnp.sum(flash_attention_latent(
            *a, block_q=512, block_k=1024).astype(jnp.float32)),
            argnums=range(5))(*a)

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        text = jax.jit(grads).lower(
            shaped(32, 128), shaped(32, 64), shaped(32, 128), shaped(1, 64),
            shaped(32, 128)).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    assert "flash_mla_fwd." in text and "flash_mla_bwd." in text
    assert "bwd_dq" not in text and "bwd_dkv" not in text


@pytest.mark.parametrize("batch,heads,kv,seq,window", [
    (4, 16, 16, 2048, None),      # ouro-2.6b-l16-ut1.seq2k-b4, a dp4 chip
    (1, 16, 16, 8192, None),      # ouro-2.6b-l16-ut1.seq8k-b1
    (2, 16, 16, 4096, None),      # olmoe-1b-7b-e64.zipf-seq4k-b2
    (1, 48, 8, 8192, None),       # Laguna's full layers
    (1, 72, 8, 8192, 512),        # Laguna's sliding layers
], ids=["seq2k-b4", "seq8k-b1", "olmoe", "laguna-full", "laguna-sliding"])
def test_fused_flash_backward_compiles_for_v5e_at_each_cells_shape(
        one_v5e, batch, heads, kv, seq, window):
    """Mosaic takes the one backward call (``flash_bwd``; ``flash_win_bwd``
    with a window) at the shape of every cell that runs it, at the blocks
    the defaults give and inside the VMEM limit the call asks for; the dq
    and dkv kernels are not in the program.  Nothing runs: no measurement."""
    from jax.experimental.compilation_cache import compilation_cache

    from multiverso_tpu.ops import flash_attention

    def shaped(h):
        return jax.ShapeDtypeStruct((batch, h, seq, 128), jnp.bfloat16,
                                    sharding=one_v5e)

    def grads(q, k, v):
        return jax.grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, window=window).astype(jnp.float32)),
            argnums=(0, 1, 2))(q, k, v)

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        text = jax.jit(grads).lower(shaped(heads), shaped(kv),
                                    shaped(kv)).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    name = "flash_bwd" if window is None else "flash_win_bwd"
    assert f"{name}." in text
    assert "bwd_dq" not in text and "bwd_dkv" not in text


def test_flash_mla_names_counter_and_refusals():
    def draw(*shape):
        return jnp.ones(shape, jnp.float32)

    args = (draw(1, 2, 64, 16), draw(1, 2, 64, 8), draw(1, 2, 64, 16),
            draw(1, 1, 64, 8), draw(1, 2, 64, 16))
    counter = metrics.counter("attention.latent_traced",
                              {"qk": "24", "v": "16"})
    fused = metrics.counter("attention.latent_bwd_traced", {"path": "fused"})
    before, fused_before = counter.value, fused.value
    text = str(jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(flash_attention_latent(*a, interpret=True))))(
            *args))
    # one block of 64 = T: the backward is the one call
    assert "flash_mla_fwd" in text and "flash_mla_bwd" in text
    assert "bwd_dq" not in text and "bwd_dkv" not in text
    assert "flash_fwd" not in text and counter.value == before + 1
    assert fused.value == fused_before + 1
    with pytest.raises(ValueError, match="k_rope \\[B,1,T,Dr\\]"):
        flash_attention_latent(args[0], args[1], args[2],
                               draw(1, 2, 64, 8), args[4])
    with pytest.raises(ValueError, match="no usable block"):
        flash_attention_latent(*(a[:, :, :7] for a in args))


def _flash_latent_lse(qn, qr, kn, kr, v, scale, block_q_bwd, block_k_bwd):
    """``flash_attention_latent`` below its wrapper, which drops lse: (o,
    lse) of the interpreted kernels, so that a test can send a cotangent
    down both."""
    B, H, T, _ = qn.shape
    blocks = [fit_block(b, T) for b in (512, 1024, block_q_bwd, block_k_bwd)]
    o, lse = fa._flash_mla(
        *(a.reshape(B * a.shape[1], T, a.shape[-1])
          for a in (qn, qr, kn, kr, v)), float(scale), H, *blocks, True)
    return o.reshape(B, H, T, -1), lse.reshape(B, H, T)


@pytest.mark.parametrize("dtype,close", [(jnp.float32, 2e-5),
                                         (jnp.bfloat16, 2e-2)],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("T,heads,bqb,bkb", [
    (128, 2, 128, 128),           # one block
    (512, 4, 128, 256),           # 4 q steps x 2 k blocks, dk_r over 4 heads
    (256, 2, 256, 64),            # one q step, 4 k blocks
    (1536, 2, 1024, 1024)],       # the blocks asked for fit to 512
    ids=["one-block", "T512-h4", "T256-k4", "T1536-fit512"])
def test_the_fused_latent_backward_against_dense_and_against_the_pair(
        monkeypatch, T, heads, bqb, bkb, dtype, close):
    """All five gradients of ``flash_mla_bwd`` in interpret mode, with a
    cotangent on lse as well as on the output (``dlse`` folds into delta):
    against dense attention in float32 on the same (rounded) inputs, and
    against the dq and dkv kernels, which the same call takes when the rule
    turns the shape away.  dk_r sums over the heads and over every k block's
    q steps inside the one call."""
    rng = np.random.RandomState(T + heads)
    B, dn, dr, dv = 2 if T <= 512 else 1, 16, 8, 16

    def draw(*shape):
        return jnp.asarray(rng.randn(*shape).astype(np.float32)).astype(
            dtype)

    args = (draw(B, heads, T, dn), draw(B, heads, T, dr),
            draw(B, heads, T, dn), draw(B, 1, T, dr), draw(B, heads, T, dv))
    w_o = draw(B, heads, T, dv).astype(jnp.float32)
    w_lse = draw(B, heads, T).astype(jnp.float32)
    scale = 0.3

    def loss(pair):
        o, lse = pair
        return jnp.sum(o.astype(jnp.float32) * w_o) + jnp.sum(lse * w_lse)

    def flash(*a):
        return loss(_flash_latent_lse(*a, scale, bqb, bkb))

    def dense(*a):
        return loss(_dense_latent_lse(*a, scale))

    counters = {path: metrics.counter("attention.latent_bwd_traced",
                                      {"path": path})
                for path in ("fused", "split")}
    before = {path: c.value for path, c in counters.items()}
    assert fit_block(bqb, T) != 1024
    with jax.default_matmul_precision("highest"):
        fused = jax.grad(flash, argnums=range(5))(*args)
        assert (counters["fused"].value, counters["split"].value) == (
            before["fused"] + 1, before["split"])
        monkeypatch.setattr(fa, "_mla_fused_fits", lambda *a: False)
        pair = jax.grad(flash, argnums=range(5))(*args)
        assert (counters["fused"].value, counters["split"].value) == (
            before["fused"] + 1, before["split"] + 1)
        want = jax.grad(dense, argnums=range(5))(
            *(a.astype(jnp.float32) for a in args))
    for got, other, ref, a in zip(fused, pair, want, args):
        assert got.shape == a.shape and got.dtype == a.dtype
        assert _rel(got, ref) < close and _rel(got, other) < close


def test_the_rule_turns_a_shape_away_and_the_pair_runs():
    """The choice is the shapes' alone: both cells' shapes take the one call;
    float32 at Ling's length, twice its length, or a q block that makes no
    whole lanes take the dq and dkv kernels, and the counter says which."""
    fits = fa._mla_fused_fits
    assert fits(8192, 128, 64, jnp.bfloat16, 1024)        # Xing's cell
    assert fits(16384, 128, 64, jnp.bfloat16, 1024)       # Ling's cell
    assert fits(8192, 128, 64, jnp.float32, 1024)
    assert not fits(16384, 128, 64, jnp.float32, 1024)
    assert not fits(32768, 128, 64, jnp.bfloat16, 1024)
    assert fits(96, 16, 8, jnp.float32, 96)
    assert not fits(96, 16, 8, jnp.float32, 32)

    def draw(*shape):
        return jnp.ones(shape, jnp.float32)

    # T = 96 under blocks of 64: they fit to 32, no whole lanes and not T
    args = (draw(1, 2, 96, 16), draw(1, 2, 96, 8), draw(1, 2, 96, 16),
            draw(1, 1, 96, 8), draw(1, 2, 96, 16))
    split = metrics.counter("attention.latent_bwd_traced", {"path": "split"})
    fused = metrics.counter("attention.latent_bwd_traced", {"path": "fused"})
    before = split.value, fused.value
    text = str(jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(
        flash_attention_latent(*a, block_q=64, block_k=64, block_q_bwd=64,
                               block_k_bwd=64, interpret=True))))(*args))
    assert "flash_mla_bwd_dq" in text and "flash_mla_bwd_dkv" in text
    assert (split.value, fused.value) == (before[0] + 1, before[1])


def test_jnp_fallback_and_dispatch_carry_the_two_widths(monkeypatch):
    rng = np.random.RandomState(0)

    def draw(*shape):
        return jnp.asarray(rng.randn(*shape).astype(np.float32))

    qn, qr, kn, kr, v = (draw(1, 2, 128, 16), draw(1, 2, 128, 8),
                         draw(1, 2, 128, 16), draw(1, 1, 128, 8),
                         draw(1, 2, 128, 24))
    want = _dense_latent(qn, qr, kn, kr, v, 0.2)
    monkeypatch.delenv("MVTPU_FORCE_FLASH", raising=False)
    got = blockwise_attention_local(qn, kn, v, 0.2, q_rope=qr, k_rope=kr)
    assert got.shape == v.shape and _rel(got, want) < 1e-5
    monkeypatch.setenv("MVTPU_FORCE_FLASH", "1")
    counter = metrics.counter("attention.traced", {"path": "interpret"})
    before = counter.value
    got = blockwise_attention_local(qn, kn, v, 0.2, q_rope=qr, k_rope=kr)
    assert counter.value == before + 1 and _rel(got, want) < 1e-5
    with pytest.raises(ValueError, match="without a window"):
        blockwise_attention_local(qn, kn, v, 0.2, q_rope=qr, k_rope=kr,
                                  window=8)


# ------------------------------------------------------------- the shares
def _moe_layer(seed: int = 0, E: int = 8, dim: int = 16, hidden: int = 8):
    full = jax.tree_util.tree_map(jnp.asarray, init_moe_params(
        dim, hidden, E, seed=seed, scoring="sigmoid"))
    rng = np.random.RandomState(seed + 1)
    full["router_bias"] = jnp.asarray(0.1 * rng.randn(E).astype(np.float32))
    for key in ("shared_w1", "shared_w3"):
        full[key] = jnp.asarray(rng.randn(dim, hidden).astype(np.float32)
                                * dim ** -0.5)
    full["shared_w2"] = jnp.asarray(rng.randn(hidden, dim).astype(np.float32)
                                    * hidden ** -0.5)
    x = jnp.asarray(rng.randn(2, 12, dim).astype(np.float32))
    return full, x


def _share(full, first, count):
    return {k: (v[first:first + count] if k in ("w1", "w3", "w2") else v)
            for k, v in full.items()}


@pytest.mark.parametrize("dispatch", ["grouped", "dense"])
def test_the_shares_add_up_to_the_uncut_layer_and_its_gradient(dispatch):
    """The 8 shares' routed parts plus the shared expert once are the uncut
    layer, forward and in the gradient of its input, router and experts."""
    full, x = _moe_layer()
    kw = dict(top_k=3, dispatch=dispatch, routed_scale=2.0, aux=False,
              scoring="sigmoid")

    def uncut(p, x):
        return moe_ffn(p, x, **kw)[0] + shared_expert(p, x, x.dtype)

    def by_shares(p, x):
        total = shared_expert(p, x, x.dtype)
        for first in range(8):
            total = total + moe_ffn(_share(p, first, 1), x,
                                    held=(first, 1), **kw)[0]
        return total

    weight = jnp.asarray(np.random.RandomState(9).randn(*x.shape),
                         jnp.float32)
    with jax.default_matmul_precision("highest"):
        assert _rel(by_shares(full, x), uncut(full, x)) < 1e-5
        got = jax.grad(lambda p, x: jnp.sum(by_shares(p, x) * weight),
                       argnums=(0, 1))(full, x)
        want = jax.grad(lambda p, x: jnp.sum(uncut(p, x) * weight),
                        argnums=(0, 1))(full, x)
    for key in ("router", "w1", "w3", "w2", "shared_w2"):
        assert _rel(got[0][key], want[0][key]) < 1e-5, key
    assert _rel(got[1], want[1]) < 1e-5
    assert not np.asarray(got[0]["router_bias"]).any()
    # a share's load with all_load: every expert's routes, whatever is held
    load = moe_ffn(_share(full, 2, 2), x, held=(2, 2), all_load=True, **kw)[3]
    assert load.shape == (8,) and int(load.sum()) == 2 * 12 * 3
    mine = moe_ffn(_share(full, 2, 2), x, held=(2, 2), **kw)[3]
    assert np.array_equal(np.asarray(mine)[:2], np.asarray(load)[2:4])


def test_the_bias_enters_the_choice_and_not_the_weights():
    full, x = _moe_layer(seed=3)
    kw = dict(top_k=3, dispatch="dense", aux=False, scoring="sigmoid",
              all_load=True)
    plain = dict(full, router_bias=jnp.zeros(8))
    pushed = dict(full, router_bias=jnp.zeros(8).at[5].set(10.0))
    load = moe_ffn(pushed, x, **kw)[3]
    assert int(load[5]) == 24                     # every token picks it now
    scores = jax.nn.sigmoid(x @ full["router"])
    assert float(jnp.max(scores + pushed["router_bias"])) > 2.0
    # ... and its weight is its score (renormalised), not score + 10
    out = moe_ffn(pushed, x, norm_topk_prob=False, **kw)[0]
    assert float(jnp.abs(out).max()) < 10 * float(
        jnp.abs(moe_ffn(plain, x, norm_topk_prob=False, **kw)[0]).max())
    with pytest.raises(ValueError, match="softmax routing's"):
        moe_ffn(full, x, top_k=3, scoring="sigmoid")
    with pytest.raises(ValueError, match="unknown router scoring"):
        moe_ffn(full, x, top_k=3, scoring="tanh", aux=False)


def test_the_bias_moves_by_the_rate_toward_the_mean_load_without_a_gradient():
    model = _model(3)
    cfg = TransformerConfig(**model)
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("dp",))
    trainer = TransformerTrainer(cfg, mesh, seed=4)
    tokens = np.asarray(_tokens(seed=6))
    loads = np.asarray(jax.jit(
        lambda p, t: _loss_routes_loads(p, t, cfg, None)[1][1])(
            trainer.params, tokens))
    assert loads.shape == (3, 8) and trainer.router_bias_absmax() == 0.0
    float(trainer.train_step_async(tokens))
    want = 0.001 * np.sign(loads.mean(axis=1, keepdims=True) - loads)
    period = trainer.params["layers"]["period"][0]["router_bias"]
    assert np.allclose(np.asarray(period), want[:2], atol=1e-9)
    assert np.allclose(
        np.asarray(trainer.params["mtp"]["layer"]["router_bias"]), want[2],
        atol=1e-9)
    assert trainer.router_bias_absmax() == pytest.approx(0.001)
    assert np.asarray(trainer.routes).shape == (3, 3)
    # another updater: the bias stays the rule's and keeps no slot's state
    momentum = TransformerTrainer(cfg, mesh, updater_type="momentum",
                                  seed=4)
    float(momentum.train_step_async(tokens))
    assert momentum.router_bias_absmax() == pytest.approx(0.001)


def test_the_rule_alone_evens_the_loads_and_moves_no_weight():
    cfg = TransformerConfig(**_model(3, max_seq=256))
    trainer = TransformerTrainer(
        cfg, Mesh(np.asarray(jax.devices()[:1]), ("dp",)), seed=4)
    tokens = np.asarray(_tokens(seed=6, seq=256))
    loads = jax.jit(lambda p, t: _loss_routes_loads(p, t, cfg, None)[1][1])

    def unevenness(params):
        counted = np.asarray(loads(params, tokens), np.float64)
        return float(np.abs(counted - counted.mean(axis=1, keepdims=True)
                            ).sum())

    before = unevenness(trainer.params)
    weights = jax.tree_util.tree_map(np.asarray, trainer.params)
    trainer.balance_router_bias(tokens, 30)
    assert unevenness(trainer.params) < 0.5 * before
    assert 0.001 <= trainer.router_bias_absmax() <= 0.030001
    for (path, was), now in zip(
            jax.tree_util.tree_flatten_with_path(weights)[0],
            jax.tree_util.tree_leaves(trainer.params)):
        if getattr(path[-1], "key", None) != "router_bias":
            assert np.array_equal(was, np.asarray(now)), path
    plain = TransformerTrainer(
        TransformerConfig(vocab_size=96, dim=32, n_layers=1, n_heads=2,
                          hidden=16, max_seq=32),
        Mesh(np.asarray(jax.devices()[:1]), ("dp",)))
    with pytest.raises(ValueError, match="no router bias"):
        plain.balance_router_bias(tokens, 1)


# ----------------------------------------------------- the residual streams
def test_resting_gates_are_the_one_stream_model():
    """``alpha = 0`` with the initial ``b``: ``H_pre`` 1/n, ``H_post`` 1,
    ``H_res`` doubly stochastic, so four equal streams stay equal and the
    logits are the one-stream model's on the same weights."""
    model = _model(3, mtp_layers=0, hc_sinkhorn_iters=20)
    cfg = TransformerConfig(**model, compute_dtype=jnp.float32)
    params = init_params(cfg, seed=7)
    rested = jax.tree_util.tree_map_with_path(
        lambda path, a: (np.zeros_like(a) if getattr(
            path[-1], "key", None) == "alpha" else a), params)
    one_cfg = TransformerConfig(**dict(model, hc_mult=0),
                                compute_dtype=jnp.float32)
    one = dict(rested)
    one["layers"] = jax.tree_util.tree_map(
        lambda lyr: {k: v for k, v in lyr.items() if not k.startswith("hc_")},
        one["layers"], is_leaf=lambda x: isinstance(x, dict)
        and "attn_norm" in x)
    tokens = _tokens()
    with jax.default_matmul_precision("highest"):
        got = transformer_forward(rested, tokens, cfg)
        want = transformer_forward(one, tokens, one_cfg)
    assert _rel(got, want) < 1e-4


def test_sinkhorn_is_doubly_stochastic_and_the_clamp_binds():
    cfg = TransformerConfig(**_model(hc_sinkhorn_iters=20))
    rng = np.random.RandomState(0)
    X = tuple(jnp.asarray(rng.randn(2, 6, 32).astype(np.float32))
              for _ in range(4))
    hc = _hc_init(cfg, Draw(jax.random.key(0)), "hc_attn")
    # at rest (the initial values) the logits are symmetric to 2e-4 and the
    # map is doubly stochastic at once
    res = _hc_gates(X, hc, cfg)[2]
    assert np.allclose(np.asarray(res.sum(axis=1)), 1.0, atol=1e-5)   # rows
    assert np.allclose(np.asarray(res.sum(axis=0)), 1.0, atol=1e-5)
    assert np.allclose(np.asarray(res[0, 0]), 1.0, atol=2e-3)
    # entries of one size (no 8 on the diagonal), moved by the streams: 20
    # iterations converge
    hc = dict(hc, phi=hc["phi"] * 50, alpha=np.full(3, 0.5, np.float32))
    level = dict(hc, b=np.where(np.asarray(hc["b"]) == 8.0, 0.0, hc["b"]))
    res = _hc_gates(X, level, cfg)[2]
    assert np.allclose(np.asarray(res.sum(axis=1)), 1.0, atol=1e-5)
    assert np.allclose(np.asarray(res.sum(axis=0)), 1.0, atol=1e-5)
    assert float(res.max()) < 0.9
    # a moved map near the identity converges slowly (the rows stay off by
    # the off-diagonal's size); the columns were divided last
    pre, post, res = _hc_gates(X, hc, cfg)
    assert pre.shape == post.shape == (4, 2, 6) and res.shape == (4, 4, 2, 6)
    assert np.allclose(np.asarray(res.sum(axis=0)), 1.0, atol=1e-5)
    assert np.allclose(np.asarray(res.sum(axis=1)), 1.0, atol=5e-3)
    assert (np.asarray(pre) > 0).all() and (np.asarray(pre) < 1).all()
    assert (np.asarray(post) > 0).all() and (np.asarray(post) < 2).all()
    # the reference's own Sinkhorn, token-major, gives the same maps
    want = xing_lm._gates(jnp.stack(X, axis=2), hc, dict(
        eps=cfg.norm_eps, clamp=(-30.0, 30.0), sinkhorn=20, hc_eps=1e-6,
        gates_dtype=None))
    assert _rel(jnp.moveaxis(res, (0, 1), (2, 3)), want[2]) < 1e-4
    assert _rel(jnp.moveaxis(pre, 0, 2), want[0]) < 1e-5
    # the clamp: a logit of 100 or of 30 is the same map, -100 the same as -30
    b = np.asarray(hc["b"]).copy()
    high, bound = b.copy(), b.copy()
    high[8 + 1], bound[8 + 1] = 100.0, 30.0
    high[8 + 6], bound[8 + 6] = -100.0, -30.0
    calm = dict(hc, alpha=np.zeros(3, np.float32))
    at_high = _hc_gates(X, dict(calm, b=high), cfg)[2]
    at_bound = _hc_gates(X, dict(calm, b=bound), cfg)[2]
    assert np.array_equal(np.asarray(at_high), np.asarray(at_bound))
    inside = _hc_gates(X, dict(calm, b=np.where(bound == 30.0, 29.0, bound)
                               ), cfg)[2]
    assert not np.array_equal(np.asarray(inside), np.asarray(at_bound))


# ------------------------------------------- the defaults are today's program
# sha256 over every leaf (path, shape, bytes) of init_params(seed=11), the
# float32 loss on RandomState(5) tokens and the summed |gradient|, taken from
# the tree before this change (commit a6fe6a6) on this machine's CPU backend;
# the lowered train step's text was also compared once, byte for byte
# (CHANGES.md, PR 32).  "laguna" holds a share of the experts: its summed
# |gradient| is PR 33's (commit a6fe6a6 read 0x1.7b64bc0000000p+10, one
# float32 step away: a share's rows now come back to their tokens by a
# float32 scatter-add over the held rows, which sums a token's rows in
# another order); tree and loss are as they were.  PR 46 drew the weights
# on the device from a seeded key, so all nine values were taken again, once,
# from its tree; the lowered train step's text, parent against change, stayed
# equal in every cell (``tools/step_hashes.py``; the old and new values are
# side by side in CHANGES.md, PR 46).
_KINDS = ["full_attention"] + ["sliding_attention"] * 3 + ["full_attention"]
BEFORE = {
    "dense": ("4755f099ae40e25a78a6b0edab9f0899b8a665fb2d0c7481543034cf654bd1c6",
              "0x1.41a5a40000000p+2", "0x1.f077980000000p+9",
              dict(vocab_size=96, dim=32, n_layers=3, n_heads=2, hidden=48,
                   max_seq=32, scan_layers=True, remat=True,
                   remat_policy="dots")),
    "olmoe": ("8886693fce0aaacad98ca08de82707c0fb42c207456f59c7661218b21f2bc2d3",
              "0x1.41af920000000p+2", "0x1.1fa5cc0000000p+9",
              dict(vocab_size=96, dim=32, n_layers=2, n_heads=2, hidden=24,
                   max_seq=32, num_experts=8, top_k=3, qk_norm=True,
                   norm_topk_prob=False, router_z_loss_coef=0.001,
                   aux_loss_coef=0.01, moe_dispatch="grouped",
                   scan_layers=True, remat=True, remat_policy="full")),
    "laguna": ("c109134a342cf0d2e6b029e0d7d32edb7d135903be3fbd8540a423434f86b650",
               "0x1.3af9de0000000p+2", "0x1.8dbf720000000p+10",
               dict(vocab_size=96, dim=32, n_layers=5, n_heads=4, head_dim=8,
                    n_kv_heads=2, hidden=16, dense_hidden=48,
                    shared_expert_hidden=16, max_seq=64, norm_eps=1e-6,
                    layer_types=_KINDS,
                    heads_per_layer=[4 if k == "full_attention" else 6
                                     for k in _KINDS],
                    mlp_layer_types=["dense"] + ["sparse"] * 4,
                    layer_period=4, sliding_window=8,
                    rope_full=dict(theta=5e5, rotary_factor=0.5,
                                   yarn_factor=8.0, original_max_seq=16,
                                   beta_fast=32.0, beta_slow=1.0,
                                   attention_factor=1.2),
                    rope_sliding=dict(theta=1e4, rotary_factor=1.0),
                    attn_gate="per_head", num_experts=8, experts_held=2,
                    experts_first=2, top_k=3, norm_topk_prob=True,
                    routed_scale=2.5, moe_dispatch="grouped",
                    aux_loss_coef=0.0, router_z_loss_coef=0.0,
                    scan_layers=True, remat=True, remat_policy="full")),
}


@pytest.mark.parametrize("name", sorted(BEFORE))
def test_without_the_new_parts_the_tree_loss_and_gradient_are_what_they_were(
        name):
    tree, loss, grad_sum, model = BEFORE[name]
    cfg = TransformerConfig(**model)
    assert (cfg.hc_mult, cfg.mtp_layers, cfg.router_scoring) == (
        0, 0, "softmax") and not cfg.rule_bias
    host = init_params(cfg, seed=11)
    digest = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(host)[0]:
        digest.update(jax.tree_util.keystr(path).encode())
        digest.update(str(leaf.shape).encode())
        digest.update(np.ascontiguousarray(leaf).tobytes())
    assert digest.hexdigest() == tree
    tokens = jnp.asarray(np.random.RandomState(5).randint(
        96, size=(2, 32)).astype(np.int32))
    got, grads = jax.jit(jax.value_and_grad(
        lambda p, t: lm_loss(p, t, cfg)))(
            jax.tree_util.tree_map(jnp.asarray, host), tokens)
    assert float(got).hex() == loss
    total = sum(jnp.sum(jnp.abs(g).astype(jnp.float32))
                for g in jax.tree_util.tree_leaves(grads))
    assert float(total).hex() == grad_sum


# ------------------------------------------------------ refusals and scopes
def test_refusals_name_their_cause():
    model = _model(3)
    mesh8 = Mesh(np.asarray(jax.devices()[:8]).reshape(2, 2, 2),
                 ("dp", "sp", "tp"))
    tokens = _tokens(batch=4)
    cfg = TransformerConfig(**model)
    with pytest.raises(ValueError, match="does not shard over 'tp'"):
        param_shardings(cfg, mesh8)
    for axes, shape, why in ((("dp", "sp"), (1, 2), "over sp=2"),
                             (("dp",), (2,), "runs on one device")):
        mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(shape), axes)
        shardings = param_shardings(cfg, mesh)     # every new leaf has one
        host = init_params(cfg)
        assert jax.tree_util.tree_structure(shardings) == \
            jax.tree_util.tree_structure(
                host, is_leaf=lambda x: isinstance(x, np.ndarray))
        with pytest.raises(ValueError, match=why):
            jax.eval_shape(lambda p: lm_loss(p, tokens, cfg, mesh), host)
    # a pipeline: refused for the streams and for the module, each by name
    for over in (dict(mtp_layers=0), dict(hc_mult=0)):
        dense = dict(_model(4, **over), layer_types=None, num_experts=0,
                     mlp_layer_types=None, router_scoring="softmax",
                     shared_expert_hidden=0, experts_held=0,
                     pipeline_microbatches=2)
        cfg = TransformerConfig(**dense)
        mesh = Mesh(np.asarray(jax.devices()[:2]), ("pp",))
        with pytest.raises(ValueError, match="hc_mult or\\s+mtp_layers"):
            jax.eval_shape(lambda p: lm_loss(p, tokens, cfg, mesh),
                           init_params(cfg))
    for wrong, why in ((dict(hc_mult=1), "at least 2 streams"),
                       (dict(mtp_layers=2), "one prediction depth"),
                       (dict(router_scoring="tanh"), "unknown router_sc"),
                       (dict(aux_loss_coef=0.01), "bias rule"),
                       (dict(kv_lora_rank=0), "latent_attention layers need"),
                       (dict(q_lora_rank=-1), "q_lora_rank >= 0"),
                       (dict(qk_norm=True), "take no n_kv_heads or qk_norm")):
        with pytest.raises(ValueError, match=why):
            TransformerConfig(**_model(3, **wrong))


def test_scopes_and_counters_are_in_the_step():
    cfg = TransformerConfig(**_model(3))
    trainer = TransformerTrainer(
        cfg, Mesh(np.asarray(jax.devices()[:1]), ("dp",)), seed=0)
    counters = {
        "latent": metrics.counter("attention.latent_traced",
                                  {"qk": "12", "v": "8"}),
        "sigmoid": metrics.counter("moe.traced", {"dispatch": "grouped",
                                                  "scoring": "sigmoid"}),
        "streams": metrics.counter("hc.traced", {"n": "4"})}
    before = {k: c.value for k, c in counters.items()}
    os.environ["MVTPU_FORCE_FLASH"] = "1"
    try:
        text = trainer.lowered_step(np.asarray(_tokens(seq=64))).as_text(
            debug_info=True)
    finally:
        del os.environ["MVTPU_FORCE_FLASH"]
    # (a differentiated top-level scope is written ``jvp(mtp)``)
    for scope in ("attn/attn.latent/", "hc.gates/", "hc.mix/", "jvp(mtp)/",
                  "mtp)/head/", "mtp)/loss/", "mtp)/checkpoint/",
                  "flash_mla_fwd", "flash_mla_bwd", "moe.route/",
                  "update/"):
        assert scope in text, scope
    # one block of 64 = T a head: the backward is the one call
    assert "flash_mla_bwd_dq" not in text and "flash_mla_bwd_dkv" not in text
    assert all(c.value > before[k] for k, c in counters.items())
    # softmax routing keeps the label set it had
    plain = metrics.counter("moe.traced", {"dispatch": "dense"})
    was = plain.value
    full, x = _moe_layer()
    moe_ffn(full, x, top_k=3, aux=False)
    assert plain.value == was + 1


# ------------------------------------------------------- the benchmark's part
def _configuration() -> dict:
    with open(CONFIG) as f:
        return json.load(f)


def test_required_work_by_hand():
    model = _configuration()["model"]
    d, H, T = 3584, 32, 8192
    assert flops_xing.latent_matmul_params(model) == (
        d * 768 + 768 * H * 192 + d * 576 + 512 * H * 256 + H * 128 * d
    ) == 28_409_856
    blocks = model["n_layers"] + 1
    assert flops_xing.blocks(model) == blocks
    assert flops_xing.ffn_kinds(model) == ["dense"] + ["sparse"] * (
        blocks - 1)
    per_block = 28_409_856 + 2 * 4 * d * 24
    sparse = d * 64 + 3 * d * 1024
    assert flops_xing.token_matmul_params(model) == (
        2 * 16384 * d + 2 * d * d + blocks * per_block + 3 * d * 9216
        + (blocks - 1) * sparse)
    pairs = T * (T + 1) // 2
    assert flops_xing.attention_flops(model, 1, T) == pytest.approx(
        3 * 640 * H * pairs * blocks)
    kernel = flops_xing.mla_kernel_flops(model, 1, T)
    assert [kernel[k] / (H * pairs * blocks) for k in ("fwd", "dq", "dkv")
            ] == pytest.approx([640, 1024, 1280])
    moved = flops_xing.mla_kernel_bytes(model, 1, T)
    head_rows = T * H
    assert moved["fwd"] / blocks == pytest.approx(
        head_rows * (192 + 128 + 128 + 128) * 2 + T * 64 * 2 + head_rows * 4)
    assert moved["dq"] - moved["fwd"] == pytest.approx(
        blocks * (head_rows * 192 * 2 + head_rows * 4))
    assert moved["dkv"] - moved["fwd"] == pytest.approx(
        blocks * (head_rows * 256 * 2 + T * 64 * 2 + head_rows * 4))
    held = 1000.0
    assert flops_xing.routed_flops(model, held) == 18 * held * d * 1024
    assert flops_xing.grouped_matmul_bytes(model, held) == pytest.approx(
        9 * ((blocks - 1) * 8 * d * 1024 + held * (d + 1024)) * 2)
    assert flops_xing.hc_mix_flops(model, 1, T) == pytest.approx(
        3 * 2 * 24 * d * 2 * blocks * T)
    assert flops_xing.train_flops(model, 1, T, held) == pytest.approx(
        6.0 * flops_xing.token_matmul_params(model) * T
        + flops_xing.routed_flops(model, held)
        + flops_xing.attention_flops(model, 1, T)
        + flops_xing.hc_mix_flops(model, 1, T))


def test_trace_reduction_books_the_latent_scopes_and_kernels():
    from benchmarks.harness import Reading
    from benchmarks.trace import latent
    from benchmarks.trace.program import ScopeIndex
    from benchmarks.trace.reduce import DeviceLines, Event, Trace

    us = 1000.0
    fusion = "%fusion.{} = bf16[8,8] fusion(...)"
    call = "%{} = bf16[8,8] custom-call(...), custom_call_target=" \
           "\"tpu_custom_call\""
    ops = [Event(fusion.format(1), 0, 10 * us),
           Event(call.format("flash_mla_fwd.3"), 10 * us, 30 * us),
           Event(call.format("flash_mla_bwd_dkv.4"), 30 * us, 45 * us),
           Event(fusion.format(5), 45 * us, 52 * us),
           Event(fusion.format(6), 52 * us, 60 * us),
           Event(fusion.format(7), 60 * us, 66 * us),
           Event(fusion.format(8), 66 * us, 90 * us)]
    body = "jit(step)/jvp(layers)/while/body/"
    index = ScopeIndex()
    index.op_names.update({
        "fusion.1": body + "attn/attn.latent/dot_general",
        "flash_mla_fwd.3": body + "attn/attn.latent/flash_mla_fwd/"
                                  "flash_mla_fwd/pallas_call",
        "flash_mla_bwd_dkv.4": "jit(step)/transpose(jvp(mtp))/checkpoint/"
                               "attn/attn.latent/flash_mla_bwd_dkv/"
                               "flash_mla_bwd_dkv/pallas_call",
        "fusion.5": body + "hc.gates/div",
        "fusion.6": "jit(step)/transpose(jvp(layers))/while/body/hc.mix/mul",
        "fusion.7": "jit(step)/jvp(mtp)/head/dot_general",
        "fusion.8": body + "mlp/dot_general"})
    trace = Trace(
        devices={"/device:TPU:0": DeviceLines(
            ops=ops, modules=[Event("jit_step(1)", 0, 45 * us),
                              Event("jit_step(1)", 45 * us, 90 * us)])},
        host=[Event("bench.window", 0, 100 * us)])
    got = latent.summarize(trace, index)
    assert got.step_programs == 2 and got.busy_s == pytest.approx(90e-6)
    assert got.by_scope_s == pytest.approx({
        "attn.latent": 45e-6, "hc.gates": 7e-6, "hc.mix": 8e-6})
    assert got.by_kernel_s == pytest.approx({
        "flash_mla_fwd": 20e-6, "flash_mla_bwd_dq": 0.0,
        "flash_mla_bwd_dkv": 15e-6})
    assert got.module_s == pytest.approx(21e-6)
    # a program without any of it (the parent) gives nothing, and raises not
    other = Trace(devices={"/device:TPU:0": DeviceLines(
        ops=ops[6:], modules=[])}, host=trace.host)
    assert latent.summarize(other, index) is None
    reading = Reading(facts={}, trace=None, peaks={}, compiles_in_window=0)
    assert latent.kernel_roofline(reading, "flash_mla_fwd") is None
    assert latent.scope_ms_per_step(reading, "hc.gates", "hc.mix") is None
    assert latent.module_ms_per_step(reading) is None


def test_benchmark_lists_the_cell_where_its_readers_are_right():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    row, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (row["config"], row["traffic"], row["chips"]) == (
        "xing4.0-29b-a4b-e8", "zipf-seq8k-b1", 1)
    assert bench["workloads"][6] is row          # later PRs' cells follow
    listed = {m["name"] for m in bench["per_layer"] + bench["end_to_end"]
              if CELL in m.get("workloads", ())}
    assert {"tokens_per_chip_s", "kernel.flash_mla_fwd_roofline",
            "kernel.flash_mla_bwd_roofline",
            "model.attn_latent_ms_per_step", "model.hc_ms_per_step",
            "model.mtp_ms_per_step", "kernel.moe_gmm_held_roofline",
            "model.moe_held_route_share", "model.moe_share",
            "model.head_loss_ms_per_step", "model.mfu_pct"} <= listed
    assert not listed & {"kernel.moe_gmm_roofline", "kernel.flash_share",
                         "kernel.flash_roofline", "kernel.flash_fwd_roofline",
                         "kernel.flash_dq_roofline",
                         "kernel.flash_dkv_roofline",
                         "kernel.flash_mla_dq_roofline",
                         "kernel.flash_mla_dkv_roofline"}
    # appended in this order; later PRs' entries follow them (the backward's
    # one roofline a family came in PR 39, where its two by call went)
    names = [m["name"] for m in bench["per_layer"]]
    first = names.index("kernel.flash_mla_fwd_roofline")
    assert names[first:first + 4] == [
        "kernel.flash_mla_fwd_roofline", "model.attn_latent_ms_per_step",
        "model.hc_ms_per_step", "model.mtp_ms_per_step"]
    assert names.index("kernel.flash_mla_bwd_roofline") > first + 3
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    from benchmarks import harness
    readers = harness.layer_readers((os.path.join(REPO, "benchmarks"),))
    loaded = harness.load_cell(CELL)
    assert {m["name"] for m in loaded.per_layer} <= set(readers)
    assert loaded.traffic["batch"] * loaded.traffic["seq"] == 8192
    declared, = [c for c in bench["configs"]
                 if c["name"] == "xing4.0-29b-a4b-e8"]
    assert sorted(declared["reduced"]) == sorted(
        _configuration()["reduced"])


def test_configuration_file_agrees_with_itself():
    from benchmarks import harness
    runner = harness.load_module((os.path.join(REPO, "benchmarks"),),
                                 "runners", "lm_train_latent")
    config = _configuration()
    runner._check_published(config)
    model = config["model"]
    cfg = TransformerConfig(**model)                 # every key is a field
    lay = cfg.layout
    assert (len(lay.lead), len(lay.period), lay.n_trail) == (1, 1, 0)
    assert lay.n_periods == model["n_layers"] - 1 >= 4          # the floor
    assert cfg.counts_routes and cfg.held == (0, 8) and cfg.rule_bias
    assert cfg.vocab_size == 16384 >= 12288   # lm_loss takes the _ce vjp
    assert sorted(config["reduced"]) == sorted([
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size", "max_position_embeddings"])
    for key in ("deployment", "assumed", "departures", "published"):
        assert config[key], key
    for wrong in (dict(kv_lora_rank=256), dict(qk_rope_head_dim=32),
                  dict(routed_scaling_factor=1.0), dict(hc_mult=2),
                  dict(scoring_func="softmax"), dict(first_k_dense_replace=2),
                  dict(num_nextn_predict_layers=0)):
        with pytest.raises(ValueError, match="model group runs"):
            runner._check_published({**config, **wrong})
    # the softmax scale's mscale, by hand: 0.1 * 1 * ln(64) + 1
    assert model["attn_mscale"] == pytest.approx(1.41589, abs=1e-5)
    # the reference's inverse frequencies are the program's; YaRN moved the
    # slow dims (/ 64) and left the fast ones
    from multiverso_tpu.models.common import Rope, rope_freqs
    want = xing_lm.inverse_frequencies(model["rope_latent"], 64)
    got = np.asarray(rope_freqs(Rope(**model["rope_latent"]), 32))
    assert np.allclose(got, want, rtol=1e-6, atol=0)
    plain = 10000.0 ** (-np.arange(32) / 32)
    assert np.allclose(want[:8], plain[:8]) and np.allclose(
        want[-4:], plain[-4:] / 64)
    # parameters by hand: ISSUE 32's count
    shapes = 2 * 16384 * 3584 + 3584 + (2 * 3584 * 3584 + 3 * 3584)
    block = 28_411_136 + 2 * (14336 * 24 + 24 + 3) + 2 * 3584
    sparse = 3584 * 64 + 64 + 3 * 3584 * 1024 * (8 + 1)
    shapes += (model["n_layers"] + 1) * block + 3 * 3584 * 9216 \
        + model["n_layers"] * sparse
    assert shapes == {7: 1_170_326_384, 6: 1_041_900_026}[model["n_layers"]]


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
        else:
            assert config["published"][key] == value, key


# ------------------------------------------------------ the cell, rehearsed
def test_the_cell_rehearses_at_toy_widths(tmp_path, monkeypatch):
    """The real runner, generator, reference and readers on the cell's own
    files shrunk to toy widths, on the CPU with the kernels interpreted:
    every check but the reference's tolerance holds as on the chip (the
    bounds there are the toy's), and the counter's metric is read."""
    import time

    from benchmarks import harness

    monkeypatch.setenv("MVTPU_FORCE_FLASH", "1")
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["paths"] = ["tinybench"]
    config = _configuration()
    config.update(hidden_size=128, num_attention_heads=4,
                  num_key_value_heads=4, intermediate_size=256,
                  moe_intermediate_size=64, q_lora_rank=48, kv_lora_rank=32,
                  qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32,
                  n_routed_experts=4, num_experts_per_tok=3, vocab_size=512,
                  max_position_embeddings=512, num_hidden_layers=3,
                  hc_sinkhorn_iters=4)
    config["published"]["router_width"] = 16
    config["rope_scaling"]["original_max_position_embeddings"] = 256
    config["model"].update(
        dim=128, n_heads=4, dense_hidden=256, hidden=64,
        shared_expert_hidden=64, q_lora_rank=48, kv_lora_rank=32,
        qk_nope_dim=32, qk_rope_dim=16, v_head_dim=32, num_experts=16,
        experts_held=4, top_k=3, vocab_size=512, max_seq=512, n_layers=3,
        layer_types=[LATENT] * 3, mlp_layer_types=["dense", "sparse",
                                                   "sparse"],
        hc_sinkhorn_iters=4)
    config["model"]["rope_latent"]["original_max_seq"] = 256
    config["trainer"].update(learning_rate=0.02, settle_steps=3,
                             balance_steps=2)
    for declared in bench["configs"]:
        if declared["name"] == config["name"]:
            declared["file"] = "tinybench/configs/xing.json"
    with open(os.path.join(REPO, "benchmarks", "traffic",
                           "zipf-seq8k-b1.json")) as f:
        traffic = json.load(f)
    traffic.update(batch=1, seq=512, check={"batch": 1, "seq": 256},
                   trace_seconds=0.5)
    for path, obj in (("tinybench/configs/xing.json", config),
                      ("tinybench/traffic/zipf-seq8k-b1.json", traffic),
                      ("BENCHMARK.json", bench)):
        os.makedirs(os.path.dirname(tmp_path / path), exist_ok=True)
        with open(tmp_path / path, "w") as f:
            json.dump(obj, f)
    cell = harness.load_cell(CELL, root=str(tmp_path))
    reference = harness.load_module(cell.search, "reference", "xing_lm")
    monkeypatch.setattr(reference, "GRAD_RTOL", 1.5)
    monkeypatch.setattr(reference, "GRAD_RTOL_ROUTED", 1.5)
    monkeypatch.setattr(reference, "LOSS_ATOL", 0.05)
    monkeypatch.setattr(reference, "BIAS_MISMATCH", 0.5)
    logged = []
    monkeypatch.setattr(harness.Runtime, "log",
                        lambda self, **fields: logged.append(fields))
    result = harness.run_cell(cell, seed=3000000007, seconds=0.5, trace=True,
                              t_start=time.perf_counter(), rehearsal=True,
                              out_root=str(tmp_path))
    assert result["correct"], [f for f in logged if "failed_checks" in f
                               or "reference_check" in f]
    assert result["failed"] == 0 and result["attempted"] >= 2
    share = result["metrics"]["model.moe_held_route_share"]["value"]
    assert 5.0 < share < 60.0                 # 25 under even routing
    check, = [f["reference_check"] for f in logged if "reference_check" in f]
    runner = harness.load_module(cell.search, "runners", "lm_train_latent")
    assert check["blocks"] == ["0", "2", "mtp"] and check["bias_blocks"] == 3
    assert sorted(k for k in check["grad_rel_err"] if runner.routed(k)) == [
        "L2.router", "L2.w2", "M.router", "M.w2"]
    assert {"L0.wkv_a", "L2.hc_attn.phi", "M.hc_mlp.b", "mtp.proj_e",
            "L0.w2", "M.shared_w2", "embed"} <= set(check["grad_rel_err"])
    assert check["bias_mismatch"] < 0.5
    # the gates' scalars are logged and held finite, not held to GRAD_RTOL
    scalars = [k for k in check["grad_rel_err"] if runner.gate_scalar(k)]
    assert len(scalars) == 12 and "L0.hc_attn.phi" not in scalars
    assert check["worst_gate_scalar"] == max(
        check["grad_rel_err"][k] for k in scalars)
    assert check["worst"] == max(
        v for k, v in check["grad_rel_err"].items()
        if not runner.routed(k) and not runner.gate_scalar(k))
    settling, = [f["settling"] for f in logged if "settling" in f]
    assert settling["steps"] == 3 and len(
        settling["held_routes_every_4th"]) == 1
    held, = [f["held_routes"] for f in logged if "held_routes" in f]
    assert held["of"] == 3 * 512 * 3 and len(held["per_layer"]) == 3
    traced, = [f["attention_traced"] for f in logged
               if "attention_traced" in f]
    assert traced["jnp"] == 0 and traced["latent"] >= 1
    parts, = [f["repeated_batch_loss_parts"] for f in logged
              if "repeated_batch_loss_parts" in f]
    assert parts[1][0] < parts[0][0] and parts[1][1] < parts[0][1]
