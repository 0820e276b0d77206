"""EvaByte's decoder on the normal path (ISSUE 40): EVA attention (one softmax
over a query's own window and chunk summaries of every earlier window,
``ops/flash_eva.py``), a float32 residual, norm gains as offsets from one and
eight byte-prediction heads, held to the plain reference
``benchmarks/reference/evabyte_lm.py``, small, on the CPU (dim 64, 4 heads x
16, window 32, chunk 4, 128 positions = four windows)."""

import json
import os
import sys
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks import flops_eva  # noqa: E402
from benchmarks.reference import evabyte_lm  # noqa: E402
from multiverso_tpu import metrics  # noqa: E402
from multiverso_tpu.models import (TransformerConfig,  # noqa: E402
                                   TransformerTrainer, init_params)
from multiverso_tpu.models.transformer import (lm_loss,  # noqa: E402
                                               transformer_forward)
from multiverso_tpu.ops import flash_eva  # noqa: E402
from multiverso_tpu.updaters import AddOption  # noqa: E402

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CONFIG = os.path.join(REPO, "benchmarks", "configs", "evabyte-6.5b-l4.json")
CELL = "evabyte-6.5b-l4.zipf-bytes-seq16k-b1"
EVA = "eva_attention"
NEW_FIELDS = dict(eva_window=0, eva_chunk=0, residual_dtype=None,
                  norm_unit_offset=False, n_pred_heads=1, logits_dtype=None,
                  init_std=0.0)


def _model(**over) -> dict:
    """The four-layer cut at toy widths."""
    model = dict(
        vocab_size=320, dim=64, n_layers=4, n_heads=4, head_dim=16,
        hidden=96, max_seq=128, norm_eps=1e-5, rope_theta=1e5,
        layer_types=[EVA] * 4, eva_window=32, eva_chunk=4, n_pred_heads=8,
        norm_unit_offset=True, residual_dtype="float32",
        logits_dtype="float32", init_std=0.05,
        scan_layers=True, remat=True, remat_policy="full")
    model.update(over)
    return model


def _cfg(model, **over):
    return TransformerConfig(**{**model, "compute_dtype": jnp.float32,
                                **over})


def _tokens(seed, batch, seq, vocab=320):
    return np.random.RandomState(seed).randint(
        0, vocab, (batch, seq)).astype(np.int32)


def _params(cfg, seed):
    """Seeded weights with every leaf away from its trivial start: gains off
    zero, ``phi`` and ``mu`` of unit size, so that the pooling is far from
    uniform."""
    rng = np.random.RandomState(seed + 1)
    params = init_params(cfg, seed=seed)
    lyr = params["layers"]
    for key in ("attn_norm", "mlp_norm"):
        lyr[key] = (0.2 * rng.randn(*lyr[key].shape)).astype(np.float32)
    for key in ("phi", "mu"):
        lyr[key] = rng.randn(*lyr[key].shape).astype(np.float32)
    params["out_norm"] = (0.2 * rng.randn(cfg.dim)).astype(np.float32)
    return jax.tree_util.tree_map(jnp.asarray, params)


def _close(got, want, what, rtol=2e-4):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    err = np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30)
    assert err < rtol, (what, err)


def _qkv(seed, B=2, H=3, T=128, D=16, dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    draw = lambda *s: jnp.asarray(rng.randn(*s), dtype)
    return (draw(B, H, T, D), draw(B, H, T, D), draw(B, H, T, D),
            jnp.asarray(rng.randn(H, D), jnp.float32),
            jnp.asarray(rng.randn(H, D), jnp.float32), draw(B, H, T, D))


def _attend(path, window=32, chunk=4, **blocks):
    """Summaries and attention through ``path`` (the dispatch is taken when
    the call is traced)."""
    def attend(q, k, v, phi, mu):
        scale = q.shape[-1] ** -0.5
        with mock.patch.object(flash_eva, "kernel_path", lambda: path):
            kbar, vbar = flash_eva.summarise(k, v, phi, mu, scale, chunk)
            return flash_eva.eva_attention(q, k, v, kbar, vbar, window,
                                           chunk, scale=scale, **blocks)
    return attend


# ------------------------------------------- (a) the attention, both passes
# The forward's summary walk (ISSUE 49) by (windows, window, chunk): 128
# summaries a window walk four tiles a group, the cell's; 2 windows are one
# tile alone, 5 a full group and every remainder, 6 a full group and one tile
# more, 8 the cell's 4 + 3; 4 summaries a window are one group of 5 tiles.
WALKS = [(2, 256, 2), (5, 256, 2), (6, 256, 2), (8, 256, 2), (6, 16, 4)]
_WALK_BLOCKS = dict(block_q=128, block_k=128, block_q_bwd=128,
                    block_k_bwd=128)


@pytest.mark.parametrize("path,blocks,walk", [
    ("jnp", {}, None),
    ("interpret", dict(block_q=16, block_k=8, block_q_bwd=8,
                       block_k_bwd=16), None),
    ("interpret", dict(block_q=32, block_k=32, block_q_bwd=32,
                       block_k_bwd=32), None),
    ("interpret", dict(block_q=8, block_k=8, block_q_bwd=16,
                       block_k_bwd=16), None),
] + [(path, blocks, walk) for walk in WALKS
     for path, blocks in (("jnp", {}), ("interpret", _WALK_BLOCKS))])
def test_both_passes_match_the_reference_softmax(path, blocks, walk):
    """The kernels (interpreted, at several block shapes) and the ``jnp``
    path against the reference's dense masked softmax over ``[own window |
    summaries]``: the output and the gradients of q, k, v, ``phi``, ``mu``;
    at the toy's four windows of 8 summaries, and at shapes that reach every
    branch of the forward's summary walk (``WALKS``)."""
    windows, window, chunk = walk or (4, 32, 4)
    q, k, v, phi, mu, d_o = (_qkv(0) if walk is None else _qkv(
        windows, B=1, H=2, T=windows * window))
    model = dict(eva_window=window, eva_chunk=chunk)
    want_o, want_g = evabyte_lm.attention_and_grads(q, k, v, phi, mu, d_o,
                                                    model)
    o, pull = jax.vjp(_attend(path, window, chunk, **blocks), q, k, v, phi,
                      mu)
    _close(o, want_o, "o")
    for name, got, want in zip("q k v phi mu".split(), pull(d_o), want_g):
        _close(got, want, f"d{name}")


@pytest.mark.parametrize("windows,window,chunk", WALKS + [(1, 32, 4)])
def test_the_forward_hands_its_statistics_back_as_a_row(windows, window,
                                                        chunk):
    """``_fwd_call``'s second result is ``[bh, T]`` float32 (a ``[bh, 1, T]``
    output of the kernel: no lane-broadcast ``[bh, T, 128]``) and is the
    logsumexp of the reference's masked scores, a window at a time."""
    T, D = windows * window, 16
    q, k, v, phi, mu, _ = _qkv(5, B=1, H=2, T=T, D=D)
    scale, per = D ** -0.5, window // chunk
    kbar, vbar = flash_eva.summarise(k, v, phi, mu, scale, chunk)
    block = min(window, 128)
    args = [x.reshape(2, x.shape[2], D) for x in (q, k, v, kbar, vbar)]
    forward = lambda *a: flash_eva._fwd_call(*a, scale, window, per, block,
                                             block, True)
    call, = [e for e in _eqns(jax.make_jaxpr(forward)(*args).jaxpr)
             if e.primitive.name == "pallas_call"]
    assert [x.aval.shape for x in call.outvars] == [(2, T, D), (2, 1, T)]
    _, lse = forward(*args)
    assert lse.shape == (2, T) and lse.dtype == jnp.float32
    t = lambda y: np.swapaxes(np.asarray(y, np.float64), 1, 2)
    rbar, _ = evabyte_lm._summaries(*map(jnp.asarray, map(t, (k, v))), phi,
                                    mu, scale, chunk, jnp.float32)
    want = np.empty((2, T))
    for w in range(windows):
        own = slice(window * w, window * (w + 1))
        keys = np.concatenate([t(k)[0, own], np.asarray(rbar)[0, :per * w]])
        s = scale * np.einsum("qhd,khd->hqk", t(q)[0, own], keys)
        col = np.arange(keys.shape[0])[None, :]
        seen = (col >= window) | (col <= np.arange(window)[:, None])
        s = np.where(seen[None], s, -np.inf)
        m = s.max(-1)
        want[:, own] = m + np.log(np.exp(s - m[..., None]).sum(-1))
    np.testing.assert_allclose(np.asarray(lse), want, rtol=2e-5, atol=2e-5)


def test_a_short_sequence_is_one_window_and_wrong_shapes_are_refused():
    q, k, v, phi, mu, _ = _qkv(1, T=24)
    for path in ("jnp", "interpret"):
        o = _attend(path, window=32, chunk=4)(q, k, v, phi, mu)
        want, _ = evabyte_lm.attention_and_grads(
            q, k, v, phi, mu, jnp.zeros_like(q),
            dict(eva_window=32, eva_chunk=4))
        _close(o, want, path)
    q, k, v, phi, mu, _ = _qkv(1, T=48)
    with pytest.raises(ValueError, match="do not divide into windows"):
        _attend("jnp")(q, k, v, phi, mu)
    kbar = jnp.zeros((2, 3, 5, 16))
    with pytest.raises(ValueError, match="eva_attention wants"):
        flash_eva.eva_attention(q, k, v, kbar, kbar, 16, 4)


@pytest.mark.parametrize("path", ["jnp", "interpret"])
def test_the_staircase_sees_the_past_through_summaries_alone(path):
    """A key moved in a later window changes no earlier output; a key of an
    earlier window reaches a later query only through its chunk's summary:
    with the summaries held still the later windows do not move, and with
    them rebuilt they do."""
    q, k, v, phi, mu, _ = _qkv(2, B=1)
    W, c, scale = 32, 4, 16 ** -0.5
    attend = _attend(path)
    base = attend(q, k, v, phi, mu)
    late = k.at[:, :, 70].add(1.0)              # window 2
    moved = attend(q, late, v, phi, mu)
    np.testing.assert_array_equal(np.asarray(moved[:, :, :64]),
                                  np.asarray(base[:, :, :64]))
    assert float(jnp.abs(moved[:, :, 70:96] - base[:, :, 70:96]).max()) > 1e-4
    early = k.at[:, :, 5].add(1.0)              # window 0, chunk 1
    kbar, vbar = flash_eva.summarise(k, v, phi, mu, scale, c)
    with mock.patch.object(flash_eva, "kernel_path", lambda: path):
        held = flash_eva.eva_attention(q, early, v, kbar, vbar, W, c,
                                       scale=scale)
    np.testing.assert_array_equal(np.asarray(held[:, :, 32:]),
                                  np.asarray(base[:, :, 32:]))
    kbar2, vbar2 = flash_eva.summarise(early, v, phi, mu, scale, c)
    changed = np.abs(np.asarray(kbar2 - kbar)).max(axis=(0, 1, 3))
    assert np.flatnonzero(changed > 0).tolist() == [1]
    rebuilt = attend(q, early, v, phi, mu)
    assert float(jnp.abs(rebuilt[:, :, 32:] - base[:, :, 32:]).max()) > 1e-4
    # and position 5's own window sees the key itself, from position 5 on
    np.testing.assert_array_equal(np.asarray(rebuilt[:, :, :5]),
                                  np.asarray(base[:, :, :5]))


@pytest.mark.parametrize("path", ["jnp", "interpret"])
def test_eva_counts_its_traces(path):
    labels = {"window": "32", "chunk": "4", "path": path}
    fwd = metrics.counter("attention.eva_traced", labels)
    bwd = metrics.counter("attention.eva_bwd_traced", labels)
    before = fwd.value, bwd.value
    q, k, v, phi, mu, d_o = _qkv(3, B=1)
    jax.vjp(_attend(path), q, k, v, phi, mu)[1](d_o)
    assert (fwd.value, bwd.value) == (before[0] + 1, before[1] + 1)


@pytest.mark.parametrize("windows,window,chunk,width,bodies", [
    (4, 32, 4, 24, 3), (8, 256, 2, 512, 4), (6, 16, 4, 20, 5),
    (1, 32, 4, 8, 1)])
def test_eva_counts_the_walk_its_shapes_give(windows, window, chunk, width,
                                             bodies):
    """``attention.eva_summary_walk_traced{width=,bodies=}``: once a trace
    through the kernels, the keys a step of the forward's summary walk takes
    (512 where the staircase's tiles and the windows allow it; a tile's
    multiple, and no more than the last window sees) and its bodies; the
    ``jnp`` path, which walks nothing, counts none."""
    walk = metrics.counter("attention.eva_summary_walk_traced",
                           {"width": str(width), "bodies": str(bodies)})
    q, k, v, phi, mu, _ = _qkv(6, B=1, H=1, T=windows * window)
    before = walk.value
    jax.eval_shape(_attend("jnp", window, chunk), q, k, v, phi, mu)
    assert walk.value == before
    jax.eval_shape(_attend("interpret", window, chunk), q, k, v, phi, mu)
    assert walk.value == before + 1
    assert flash_eva._walk_group(window // chunk, windows) == bodies


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in eqn.params.values():
            for j in (sub if isinstance(sub, (list, tuple)) else [sub]):
                inner = getattr(j, "jaxpr", j)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


@pytest.mark.parametrize("name", ["flash_eva_fwd", "flash_eva_bwd"])
def test_the_kernels_hold_the_stated_precision(name):
    """Read off the kernel's own jaxpr on bfloat16 inputs (``mixedp_attn``):
    every product has bfloat16 operands and a float32 result, every
    exponential is float32 in and out (scores and statistics are never
    rounded), every accumulator is float32 VMEM, and the gradients leave in
    the inputs' dtype.  The pooling's softmax, outside the kernels, is
    float32 too."""
    q, k, v, phi, mu, d_o = _qkv(4, B=1, dtype=jnp.bfloat16)
    traced = jax.make_jaxpr(lambda *a: jax.vjp(
        _attend("interpret"), *a[:5])[1](a[5]))(q, k, v, phi, mu, d_o)
    (call,) = [e for e in _eqns(traced.jaxpr)
               if e.primitive.name == "pallas_call"
               and e.params["name"] == name]
    eqns = list(_eqns(call.params["jaxpr"]))
    exps = [e for e in eqns if e.primitive.name == "exp"]
    assert len(exps) >= 2
    for eqn in exps:
        assert eqn.invars[0].aval.dtype == jnp.float32
        assert eqn.outvars[0].aval.dtype == jnp.float32
    dots = [e for e in eqns if e.primitive.name == "dot_general"]
    assert len(dots) >= (4 if name == "flash_eva_fwd" else 10)
    for eqn in dots:
        assert {var.aval.dtype for var in eqn.invars} == {
            jnp.dtype(jnp.bfloat16)}
        assert eqn.outvars[0].aval.dtype == jnp.float32
    scratch = [var.aval for var in call.params["jaxpr"].invars
               if "vmem" in str(var.aval) and var.aval.dtype == jnp.float32]
    assert len(scratch) >= (3 if name == "flash_eva_fwd" else 5)
    if name == "flash_eva_bwd":
        assert [x.aval.dtype for x in call.outvars] == [jnp.bfloat16] * 5
    outside = [e for e in _eqns(traced.jaxpr) if e.primitive.name == "exp"
               and e not in eqns]
    assert outside and all(e.invars[0].aval.dtype == jnp.float32
                           for e in outside)


# ----------------------------------------------- (b) the whole small model
@pytest.mark.parametrize("path", ["jnp", "interpret"])
def test_the_four_layer_model_matches_the_reference_loss_and_gradients(
        path, monkeypatch):
    """Program against ``evabyte_lm`` on seeded weights, float32 on both
    sides: the loss and EVERY leaf's gradient, ``phi`` and ``mu`` among them,
    at four windows."""
    if path == "interpret":
        monkeypatch.setenv("MVTPU_FORCE_FLASH", "1")
    model = _model()
    cfg = _cfg(model)
    params, tokens = _params(cfg, 0), jnp.asarray(_tokens(0, 2, 128))
    want_loss, want, _ = evabyte_lm.loss_and_grads(params, tokens, model)
    loss, grads = jax.value_and_grad(lm_loss)(params, tokens, cfg)
    assert abs(float(loss) - float(want_loss)) < 2e-5
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    ref = dict(jax.tree_util.tree_flatten_with_path(want)[0])
    assert len(flat) == 14
    for key, got in flat:
        _close(got, ref[key], jax.tree_util.keystr(key), rtol=5e-4)
        assert float(jnp.abs(got).max()) > 0, key


def test_the_eight_heads_loss_is_eight_plain_cross_entropies():
    model = _model()
    cfg = _cfg(model)
    params, tokens = _params(cfg, 1), _tokens(1, 2, 128)
    logits = np.asarray(transformer_forward(params, jnp.asarray(tokens), cfg),
                        np.float64)
    assert logits.shape == (2, 128, 8 * 320)
    total = 0.0
    for i in range(8):
        lf = logits[:, :128 - 1 - i, 320 * i:320 * (i + 1)]
        target = tokens[:, 1 + i:]
        logz = np.log(np.exp(lf - lf.max(-1, keepdims=True)).sum(-1)) + lf.max(
            -1)
        picked = np.take_along_axis(lf, target[..., None], -1)[..., 0]
        total += (logz - picked).mean() / 8
    assert abs(float(lm_loss(params, jnp.asarray(tokens), cfg)) - total) < 1e-5
    # the logits' dtype is a field of its own, not the heads' count's
    for over, dtype in ((dict(), jnp.float32),
                        (dict(logits_dtype=None), jnp.bfloat16)):
        assert transformer_forward(
            params, jnp.asarray(tokens),
            _cfg({**model, **over}, compute_dtype=jnp.bfloat16)
        ).dtype == dtype


def test_a_lower_precision_is_another_result_and_the_step_says_which():
    """The reference's switches move what they should: bfloat16 softmax
    statistics move ``mu``'s gradient well past what ``compute`` alone (the
    program's own operand type) costs; a bfloat16 residual is another result
    too, but within a fifth of that cost on every leaf (no limit stands
    between them), so the runner reads the stream's dtype off the step's own
    text (``stream_dtypes``), which tells a bfloat16 stream at once."""
    from benchmarks.runners import lm_train_eva

    model = _model()
    params = _params(_cfg(model), 2)
    tokens = jnp.asarray(_tokens(2, 1, 128))
    _, grads, rows = evabyte_lm.loss_and_grads(params, tokens, model)

    def far(**switches):
        _, g, r = evabyte_lm.loss_and_grads(params, tokens, model, **switches)
        d = np.asarray(g["layers"]["mu"] - grads["layers"]["mu"])
        return (np.linalg.norm(d) / np.linalg.norm(grads["layers"]["mu"]),
                float(jnp.abs(r - rows).max()))

    plain = far(compute="bfloat16")
    assert far(compute="bfloat16", stats="bfloat16")[0] > 1.3 * plain[0]
    stream = far(compute="bfloat16", residual="bfloat16")
    assert stream[1] != plain[1] and stream[0] < 1.5 * plain[0]
    for residual in ("float32", None):
        cfg = TransformerConfig(**{**model, "residual_dtype": residual})
        text = jax.jit(lambda p, t, cfg=cfg: jax.value_and_grad(lm_loss)(
            p, t, cfg)).lower(params, tokens).as_text()
        found = lm_train_eva.stream_dtypes(text, 1, 128, model)
        assert found["logits"] == ["f32"] and found["rounded"] == 0
        assert lm_train_eva.stream_is_float32(found) == (
            residual is not None), found
        assert found["carries"] == (["f32"] if residual else ["bf16"])
        assert found["f32_adds"] or found["bf16_adds"] >= 4


@pytest.mark.parametrize("fault,sound", [
    (None, True), ("handed back rounded", False),
    ("rounded before the next sum", False), ("a bfloat16 carry", False)])
def test_the_text_walk_tells_a_stream_rounded_between_layers(fault, sound):
    """``stream_dtypes`` on a scan of two residual sums a layer written by
    hand: float32 sums alone do not pass it.  A stream that goes through
    bfloat16 between the layers (at the end of the scan's body, or between a
    layer's two sums) has float32 sums and a float32 carry and is told by the
    walk all the same; a bfloat16 carry is told by its dtype."""
    from benchmarks.runners import lm_train_eva

    bf, f32 = jnp.bfloat16, jnp.float32
    model = dict(dim=64, n_pred_heads=8, vocab_size=320)
    carry = bf if fault == "a bfloat16 carry" else f32

    def sub(x, w):                 # a sub-layer: reads bfloat16, gives it
        return jnp.tanh(x.astype(bf) @ w)

    def layer(x, w):
        x = x.astype(f32) + sub(x, w).astype(f32)
        if fault == "rounded before the next sum":
            x = x.astype(bf).astype(f32)
        x = x + sub(x, w).astype(f32)
        if fault == "handed back rounded":
            x = x.astype(bf).astype(f32)
        return x.astype(carry), None

    def step(x, ws, head):
        x = jax.lax.scan(jax.checkpoint(layer), x.astype(carry), ws)[0]
        return jnp.sum(jnp.dot(x.astype(bf), head,
                               preferred_element_type=f32))

    text = jax.jit(jax.grad(step, argnums=1)).lower(
        jnp.zeros((1, 128, 64), f32), jnp.zeros((4, 64, 64), bf),
        jnp.zeros((64, 2560), bf)).as_text()
    found = lm_train_eva.stream_dtypes(text, 1, 128, model)
    assert found["f32_adds"] >= 4 and found["logits"] == ["f32"], found
    assert lm_train_eva.stream_is_float32(found) == sound, found
    assert (found["rounded"] > 0) == (fault is not None), found
    assert found["carries"] == (
        ["bf16"] if fault == "a bfloat16 carry" else ["f32"])


@pytest.mark.parametrize("fault,reads", [
    (None, 0.0), ("pool_unchanged", 1.0), ("twice_the_rate", 1.0),
    ("the_wrong_way", 2.0), ("another_gradient", 1.4)])
def test_the_step_check_tells_an_updater_at_fault(fault, reads):
    """``step_compare`` on a step written by hand: float32 leaves whose
    gradients stand near float32's spacing of the parameter over the rate (so
    that (old - new) / lr would read the spacing): the updater's own
    arithmetic reads 0, a leaf left as it was 1, a step the wrong way 2."""
    from benchmarks.reference import evabyte_lm
    from benchmarks.runners import lm_train_eva

    rng = np.random.RandomState(7)
    lr, f32 = 1e-3, np.float32
    before = {f"L0.{key}": rng.randn(64, 16).astype(f32)
              for key in ("wq", "phi", "mu")}
    grads = {k: (1e-4 * rng.randn(*v.shape)).astype(f32)   # lr g ~ an ulp
             for k, v in before.items()}
    after = {k: before[k] - f32(lr) * grads[k] for k in before}
    wide = lambda tree: {k: v.astype(np.float64) for k, v in tree.items()}
    step = {"loss": 5.0, "before": wide(before), "after": wide(after)}
    prog = {"loss": 5.0, "grads": wide(grads)}
    naive = np.linalg.norm((before["L0.phi"] - after["L0.phi"]) / lr
                           - grads["L0.phi"]) / np.linalg.norm(
                               grads["L0.phi"])
    assert naive > 0.2                      # what the quotient would read
    if fault is None:
        found = lm_train_eva.step_compare(step, prog, lr, evabyte_lm)
        assert found["ok"] and found["worst"] == 0 and not found["unresolved"]
        return
    found = lm_train_eva.step_controls(step, prog, lr, evabyte_lm)[fault]
    assert not found["ok"] and abs(found["worst"] - reads) < 0.15, found
    if fault == "pool_unchanged":
        assert found["rel_err"]["L0.wq"] == 0
        assert found["rel_err"]["L0.phi"] == found["rel_err"]["L0.mu"] == 1


@pytest.mark.parametrize("path", ["jnp", "interpret"])
def test_the_trainer_steps_the_model_and_the_pooling_learns(path,
                                                            monkeypatch):
    """``TransformerTrainer`` with scan, remat "full" and the SGD updater in
    bfloat16 compute: the loss falls on a repeated batch, the stream is
    float32, and ``phi`` and ``mu`` move."""
    if path == "interpret":
        monkeypatch.setenv("MVTPU_FORCE_FLASH", "1")
    cfg = TransformerConfig(**_model())
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("dp",))
    trainer = TransformerTrainer(cfg, mesh,
                                 option=AddOption(learning_rate=0.05), seed=3)
    before = jax.tree_util.tree_map(np.asarray, trainer.params["layers"])
    assert np.all(before["attn_norm"] == 0) and before["phi"].std() > 0.1
    assert trainer.params["head"].shape == (64, 8 * 320)
    assert abs(float(np.std(np.asarray(trainer.params["head"]))) - 0.05) < 2e-3
    tokens = _tokens(3, 2, 128)
    text = trainer.lowered_step(tokens).as_text()
    assert "tensor<2x128x64xf32>" in text          # the float32 stream
    losses = [trainer.train_step(tokens) for _ in range(4)]
    assert losses[-1] < losses[0] and np.all(np.isfinite(losses))
    after = trainer.params["layers"]
    for key in ("phi", "mu", "attn_norm", "wq"):
        assert float(jnp.abs(after[key] - before[key]).max()) > 0, key


@pytest.mark.parametrize("name", ["attn/attn.eva/", "attn.eva.summarise/",
                                  "flash_eva_fwd", "flash_eva_bwd",
                                  "rematted_computation/attn/attn.eva/"])
def test_the_step_names_its_scopes_and_kernels(name, monkeypatch):
    """The names the trace walk reads are in the lowered step, the replayed
    forward's among them (kernels interpreted: the same scopes and call
    names as on the chip)."""
    monkeypatch.setenv("MVTPU_FORCE_FLASH", "1")
    cfg = TransformerConfig(**_model(n_layers=2, layer_types=[EVA] * 2))
    trainer = TransformerTrainer(
        cfg, Mesh(np.asarray(jax.devices()[:1]), ("dp",)), seed=0)
    text = trainer.lowered_step(_tokens(0, 1, 64)).as_text(debug_info=True)
    assert name in text


def test_lengths_no_chunk_or_window_divides_are_padded_past_every_query():
    model = _model(max_seq=128)
    cfg = _cfg(model)
    params = _params(cfg, 4)
    tokens = jnp.asarray(_tokens(4, 1, 128))
    whole = transformer_forward(params, tokens, cfg)
    for seq in (30, 80):       # no chunk divides 30; 80 is 2.5 windows
        short = transformer_forward(params, tokens[:, :seq], cfg)
        _close(short, whole[:, :seq], f"logits of {seq}", rtol=1e-5)


# --------------------------------------- the new fields at their defaults
@pytest.mark.parametrize("kind", ["dense", "routed"])
def test_the_new_fields_at_their_defaults_trace_the_program_they_traced(
        kind):
    """A dense and a routed tiny configuration lower to the same text with
    the new fields spelled out at their defaults as without them, and draw
    the same weights."""
    base = dict(vocab_size=96, dim=32, n_layers=2, n_heads=2, hidden=48,
                scan_layers=True, remat=True)
    if kind == "routed":
        base.update(hidden=16, num_experts=8, top_k=2, moe_dispatch="grouped")
    texts, drawn = [], []
    for model in (base, {**base, **NEW_FIELDS}):
        cfg = TransformerConfig(**model)
        params = init_params(cfg, seed=0)
        drawn.append(params)
        texts.append(jax.jit(lambda p, t, cfg=cfg: jax.value_and_grad(
            lm_loss)(p, t, cfg)).lower(
                params, jnp.asarray(_tokens(0, 1, 64, 96))).as_text())
    assert texts[0] == texts[1]
    assert "f32" in texts[0] and "flash_eva" not in texts[0]
    for a, b in zip(jax.tree_util.tree_leaves(drawn[0]),
                    jax.tree_util.tree_leaves(drawn[1])):
        np.testing.assert_array_equal(a, b)
    assert np.all(drawn[0]["out_norm"] == 1)


# ------------------------------------------------------------------ refusals
@pytest.mark.parametrize("over,match", [
    (dict(eva_chunk=0), "eva_window a multiple of eva_chunk"),
    (dict(eva_window=30), "eva_window a multiple of eva_chunk"),
    (dict(n_kv_heads=2), "take no n_kv_heads or qk_norm"),
    (dict(qk_norm=True), "take no n_kv_heads or qk_norm"),
    (dict(n_pred_heads=0), "at least one head"),
    (dict(mtp_layers=1), "parallel heads or a prediction module"),
    (dict(layer_types=["eva"] * 4), "unknown layer kind"),
])
def test_the_configuration_refuses_by_name(over, match):
    with pytest.raises(ValueError, match=match):
        _cfg(_model(**over))


@pytest.mark.parametrize("axes,shape,match", [
    (("dp", "sp"), (1, 2), "eva_attention runs on one device.*'sp': 2"),
    (("dp", "tp"), (1, 2), "eva_attention runs on one device.*'tp': 2"),
    (("dp",), (2,), "eva_attention runs on one device.*'dp': 2"),
])
def test_layouts_the_eva_kind_does_not_support_refuse_it(axes, shape, match):
    cfg = _cfg(_model(n_layers=2, layer_types=[EVA] * 2))
    mesh = Mesh(np.asarray(jax.devices()[:int(np.prod(shape))]).reshape(
        shape), axes)
    with pytest.raises(ValueError, match=match):
        trainer = TransformerTrainer(cfg, mesh, seed=0)
        trainer.train_step(_tokens(0, 2, 64))


def test_a_float32_stream_refuses_what_passes_the_compute_dtype():
    model = _model(n_layers=2, layer_types=None, eva_window=0, eva_chunk=0,
                   n_pred_heads=1, hc_mult=2)
    cfg = _cfg(model, compute_dtype=jnp.bfloat16)
    with pytest.raises(ValueError, match="residual_dtype does not compose"):
        lm_loss(init_params(cfg, 0), jnp.asarray(_tokens(0, 1, 64)), cfg)


# ------------------------------------------------- the configuration's file
def test_the_configuration_file_holds_the_published_numbers():
    with open(CONFIG) as f:
        config = json.load(f)
    with open(CATALOG) as f:
        row, = [r for r in map(json.loads, f) if r["name"] == "EvaByte"]
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        declared, = [c for c in json.load(f)["configs"]
                     if c["name"] == "evabyte-6.5b-l4"]
    assert declared["source"] == config["source"] == row["source_url"]
    assert set(declared["reduced"]) == set(config["reduced"]) == {
        "num_hidden_layers", "max_position_embeddings", "max_seq_length"}
    for key, value in row["config"].items():
        if key not in declared["reduced"]:
            assert config[key] == value, key
    assert config["published"]["num_hidden_layers"] == row["config"][
        "num_hidden_layers"] == 32
    model = config["model"]
    assert (model["dim"], model["n_heads"], model["head_dim"],
            model["hidden"], model["vocab_size"], model["eva_window"],
            model["eva_chunk"], model["n_pred_heads"]) == (
                4096, 32, 128, 11008, 320, 2048, 16, 8)
    assert {"head_dim", "pooling", "aggregation", "init", "optimizer",
            "heads", "rotary", "precision"} <= set(config["assumed"])
    assert config["deployment"] and config["departures"]
    # 821.4 M parameters, counted from the program's own tree at these widths
    shapes = _published_shapes(model)
    assert shapes["head"] == (4096, 2560) and shapes["embed"] == (320, 4096)
    assert shapes["layers"]["phi"] == (4, 32, 128)
    assert shapes["layers"]["w1"] == (4, 4096, 11008)
    total = sum(int(np.prod(s)) for s in jax.tree_util.tree_leaves(
        shapes, is_leaf=lambda x: isinstance(x, tuple)))
    assert total == 821_366_784
    assert total - 1_310_720 - 36_864 - 32_768 == flops_eva.matmul_params(
        model)
    peak = config["compiled_peak_gib"]
    assert 0.25 * 15.75 <= peak <= 15.75


def _published_shapes(model):
    """The program's parameter tree at the published widths, as shapes: its
    own ``init_params`` at toy widths (which draws), every toy width put
    back."""
    small = init_params(TransformerConfig(**{
        **model, "dim": 64, "hidden": 96, "head_dim": 2, "max_seq": 64}), 0)
    widths = {64: model["dim"], 96: model["hidden"], 2: model["head_dim"]}
    return jax.tree_util.tree_map(
        lambda a: tuple(widths.get(n, n) for n in a.shape), small)


def _brute_pairs(seq, window, chunk):
    t = np.arange(seq)
    own = ((t[None, :] <= t[:, None])
           & (t[None, :] // window == t[:, None] // window)).sum()
    m = np.arange(seq // chunk)
    summary = (m[None, :] < (window // chunk) * (t[:, None] // window)).sum()
    return {"own": int(own), "summary": int(summary)}


@pytest.mark.parametrize("seq,window,chunk", [
    (128, 32, 4), (96, 32, 8), (80, 32, 4), (24, 32, 4), (512, 128, 16)])
def test_the_pairs_are_counted_as_a_brute_force_mask_counts_them(
        seq, window, chunk):
    assert flops_eva.pairs(seq, window, chunk) == _brute_pairs(seq, window,
                                                               chunk)


def test_the_step_is_counted_by_hand():
    with open(CONFIG) as f:
        model = json.load(f)["model"]
    p = flops_eva.pairs(16384, 2048, 16)
    assert p == {"own": 8 * 2048 * 2049 // 2, "summary": 2048 * 128 * 28}
    work = flops_eva.eva_flops(model, 1, 16384)
    assert work["fwd"] == (p["own"] + p["summary"]) * 32 * 4 * 512
    assert work["bwd"] == 2 * work["fwd"]
    step = flops_eva.train_flops(model, 1, 16384)
    assert 0.05 < (work["fwd"] + work["bwd"]) / step < 0.06    # ~5.5%
    moved = flops_eva.eva_bytes(model, 1, 16384)
    # compute-bound on a v5e, both passes
    assert work["fwd"] / 1.97e14 > moved["fwd"] / 8.19e11
    assert work["bwd"] / 1.97e14 > moved["bwd"] / 8.19e11


# --------------------------------------------------------- compiled for v5e
@pytest.fixture(scope="module")
def one_v5e():
    """A described v5e chip (nothing attached): the TPU's own compiler runs
    here."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                    # no libtpu, or another chip
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e}")
    return topo.devices[0]


def test_the_cells_step_compiles_for_v5e(one_v5e, monkeypatch):
    """The cell's own step at 1 x 16,384 and the published widths, through
    ``TransformerTrainer._raw_step`` (``benchmarks/tests/test_aot.py``'s
    manner): Mosaic takes both kernels, the step holds three calls (the
    forward, its replay under remat "full", the backward), fits the chip and
    fills a quarter of it, and is the peak the configuration's file states;
    the runner's walk of its lowered text finds the stream float32 from end to
    end.  Nothing runs: no measurement."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmarks import harness
    from multiverso_tpu.models.transformer import param_shardings
    from multiverso_tpu.updaters import get_updater

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("MVTPU_FORCE_FLASH", raising=False)
    monkeypatch.delenv("MVTPU_NO_FLASH", raising=False)
    cell = harness.load_cell(CELL)
    model, traffic = cell.config["model"], cell.traffic
    cfg = TransformerConfig(**model)
    mesh = Mesh(np.asarray([one_v5e]), ("dp",))
    trainer = TransformerTrainer.__new__(TransformerTrainer)
    trainer.cfg, trainer.mesh = cfg, mesh
    trainer.updater = get_updater(cell.config["trainer"]["updater_type"])
    trainer.option = AddOption(
        learning_rate=cell.config["trainer"]["learning_rate"])
    params = jax.tree_util.tree_map(
        lambda shape, sharding: jax.ShapeDtypeStruct(
            shape, jnp.float32, sharding=sharding),
        _published_shapes(model), param_shardings(cfg, mesh),
        is_leaf=lambda x: isinstance(x, tuple))
    state = jax.tree_util.tree_map(lambda p: (), params)
    tokens = jax.ShapeDtypeStruct(
        (traffic["batch"], traffic["seq"]), jnp.int32,
        sharding=NamedSharding(mesh, P("dp", None)))
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        lowered = jax.jit(trainer._raw_step(), donate_argnums=(0, 1)).lower(
            params, state, tokens)
        lowered_text = lowered.as_text()
        assert lowered_text.count("tpu_custom_call") == 3
        compiled = lowered.compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    from benchmarks.runners import lm_train_eva

    found = lm_train_eva.stream_dtypes(lowered_text, traffic["batch"],
                                       traffic["seq"], model)
    assert lm_train_eva.stream_is_float32(found), found
    text = compiled.as_text()
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    for name in ("flash_eva_fwd", "flash_eva_bwd"):
        assert any(f"/{name}" in line for line in calls), (name, calls)
    peak = harness.compiled_peak_bytes(compiled) / 2 ** 30
    assert 0.25 * 15.75 < peak <= 15.75
    assert abs(peak - cell.config["compiled_peak_gib"]) < 0.01, peak


# ------------------------------------------------------ the cell, rehearsed
def test_the_cell_rehearses_at_toy_widths(tmp_path, monkeypatch):
    """The real runner, generator, reference and readers on the cell's own
    files shrunk to toy widths, on the CPU with the kernels interpreted:
    every check holds as on the chip."""
    import time

    from benchmarks import harness

    monkeypatch.setenv("MVTPU_FORCE_FLASH", "1")
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["paths"] = ["tinybench"]
    with open(CONFIG) as f:
        config = json.load(f)
    config.update(hidden_size=64, num_attention_heads=4,
                  num_key_value_heads=4, intermediate_size=96,
                  max_position_embeddings=256, max_seq_length=256,
                  window_size=32, chunk_size=4, init_std=0.05)
    config["model"].update(dim=64, n_heads=4, head_dim=16, hidden=96,
                           max_seq=256, eva_window=32, eva_chunk=4,
                           init_std=0.05)
    config["trainer"].update(learning_rate=0.02)
    for declared in bench["configs"]:
        if declared["name"] == config["name"]:
            declared["file"] = "tinybench/configs/evabyte.json"
    with open(os.path.join(REPO, "benchmarks", "traffic",
                           "zipf-bytes-seq16k-b1.json")) as f:
        traffic = json.load(f)
    traffic.update(batch=1, seq=256, check={"batch": 1, "seq": 128},
                   trace_seconds=0.5)
    for path, obj in (("tinybench/configs/evabyte.json", config),
                      ("tinybench/traffic/zipf-bytes-seq16k-b1.json",
                       traffic),
                      ("BENCHMARK.json", bench)):
        os.makedirs(os.path.dirname(tmp_path / path), exist_ok=True)
        with open(tmp_path / path, "w") as f:
            json.dump(obj, f)
    cell = harness.load_cell(CELL, root=str(tmp_path))
    assert cell.runner == "lm_train_eva" and cell.chips == 1
    # the bounds are the published widths'; the toy's own are wider
    reference = harness.load_module(cell.search, "reference", "evabyte_lm")
    for bound, toy in (("GRAD_RTOL", 0.5), ("GRAD_RTOL_POOL", 0.8),
                       ("LOSS_ATOL", 0.02), ("LOGITS_RTOL", 0.2),
                       ("ATTN_RTOL", 0.05), ("ATTN_GRAD_RTOL", 0.1),
                       ("ATTN_PHI_RTOL", 0.1), ("ATTN_SCALED_RTOL", 0.05),
                       ("ATTN_SCALED_GRAD_RTOL", 0.1)):
        monkeypatch.setattr(reference, bound, toy)
    logged = []
    monkeypatch.setattr(harness.Runtime, "log",
                        lambda self, **fields: logged.append(fields))
    result = harness.run_cell(cell, seed=3000000007, seconds=0.5, trace=True,
                              t_start=time.perf_counter(), rehearsal=True,
                              out_root=str(tmp_path))
    check, = [f["reference_check"] for f in logged if "reference_check" in f]
    assert result["correct"], (
        [f for f in logged if "failed_checks" in f or "repeated_batch_losses"
         in f], sorted((k, v) for k, v in check.items()
                       if k not in ("grad_rel_err", "logits_rel_err")))
    assert result["failed"] == 0 and result["attempted"] >= 2
    assert check["shape"] == [1, 128] and len(check["logits_rel_err"]) == 16
    # every leaf: 11 a layer, and the embedding, the head, the final gain
    assert len(check["grad_rel_err"]) == 4 * 11 + 3
    assert {"L0.phi", "L3.mu", "L1.wq", "L2.w2", "embed",
            "head"} <= set(check["grad_rel_err"])
    assert set(result["compared"]) == {
        "loss_abs_err", "median_logits", "worst", "worst_pool", "step.worst",
        "attention.out_rel_err", "attention.worst_grad", "attention.dphi",
        "attention_scaled.out_rel_err", "attention_scaled.worst_grad",
        "compiles_in_window"}
    # the timed program's own step moved every leaf by its gradient
    step = check["step"]
    assert set(step["rel_err"]) == set(check["grad_rel_err"])
    assert not step["unresolved"] and step["worst"] < 0.05, step
    traced, = [f["attention_traced"] for f in logged
               if "attention_traced" in f]
    assert traced["eva_jnp"] == 0 and traced["eva_bwd_jnp"] == 0
    assert traced["eva_interpret"] >= 1 and traced["eva_bwd_interpret"] >= 1
    runner = harness.load_module(cell.search, "runners", "lm_train_eva")
    found = runner.controls(
        reference, cell.config["model"],
        {"attention_scaled": runner.scaled_inputs(cell.config["model"], 128,
                                                  5)}, names=("stats_bf16",))
    assert found["stats_bf16"]["attention_scaled"]["out_rel_err"] > 0


# ------------------------------------------------- the readers of the trace
def _fake_trace():
    """Two steps' worth of device events on one chip, by hand: names as the
    compiled step gives them (``op_name``s of the sandbox's v5e compile)."""
    from benchmarks.trace import reduce as R

    ms = 1e6
    ops, op_names, t = [], {}, 0.0
    body = "jit(step)/jvp(layers)/while/body/closed_call/attn/attn.eva/"
    back = ("jit(step)/transpose(jvp(layers))/while/body/closed_call/"
            "checkpoint/")
    for name, op_name, dur in (
            ("flash_eva_fwd.1", body + "flash_eva_fwd/pallas_call", 4),
            ("fusion.3", body + "dot_general", 10),
            ("fusion.5", body + "attn.eva.summarise/reduce_sum", 3),
            ("flash_eva_fwd.2", back + "rematted_computation/attn/attn.eva/"
             "flash_eva_fwd/pallas_call", 4),
            ("flash_eva_bwd.4", back + "attn/attn.eva/flash_eva_bwd/"
             "pallas_call", 9),
            ("fusion.6", back + "attn/attn.eva/flash_eva_bwd/reduce_sum", 1),
            ("fusion.8", back + "attn/attn.eva/attn.eva.summarise/mul", 2),
            ("fusion.13", "jit(step)/jvp(layers)/while/body/closed_call/mlp/"
             "dot_general", 8)):
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


def test_the_trace_walk_books_the_scopes_and_both_passes():
    from benchmarks.trace import eva

    found = eva.summarize(*_fake_trace())
    assert found.step_programs == 2
    # all time under a pass, kernel or fusion; the replayed forward too
    assert found.by_pass_s == pytest.approx({"flash_eva_fwd": 2 * 8e-3,
                                             "flash_eva_bwd": 2 * 10e-3})
    assert found.by_scope_s == pytest.approx({
        "attn.eva": 2 * 33e-3, "attn.eva.summarise": 2 * 5e-3})
    assert eva._booked("a/flash_eva_bwd_sum/pallas_call") == (
        True, False, "flash_eva_bwd")
    assert eva._booked("a/attn/flash_fwd/pallas_call") == (False, False,
                                                           None)


def test_the_readers_read_the_facts_and_leave_out_what_is_not_there(
        monkeypatch):
    from benchmarks import harness
    from benchmarks.trace import eva

    with open(CONFIG) as f:
        model = json.load(f)["model"]
    found = eva.summarize(*_fake_trace())
    monkeypatch.setattr(eva, "of_reading", lambda reading: found)
    peaks = {"bf16_flops_per_s": 1.97e14, "hbm_bytes_per_s": 8.19e11}
    facts = {"chips": 1,
             "eva_flops_per_step": flops_eva.eva_flops(model, 1, 16384),
             "eva_bytes_per_step": flops_eva.eva_bytes(model, 1, 16384)}
    reading = harness.Reading(facts=facts, trace=object(), peaks=peaks,
                              compiles_in_window=0)
    readers = harness.layer_readers((harness.HERE,))
    assert readers["model.attn_eva_ms_per_step"].read(
        reading) == pytest.approx(33.0)
    assert readers["model.eva_summarise_ms_per_step"].read(
        reading) == pytest.approx(5.0)
    work = facts["eva_flops_per_step"]
    assert readers["kernel.flash_eva_fwd_roofline"].read(
        reading) == pytest.approx(100 * work["fwd"] / 1.97e14 / 8e-3)
    assert readers["kernel.flash_eva_bwd_roofline"].read(
        reading) == pytest.approx(100 * work["bwd"] / 1.97e14 / 10e-3)
    # a program without the scopes (the parent), or a run without a trace
    monkeypatch.setattr(eva, "of_reading", lambda reading: None)
    names = ("model.attn_eva_ms_per_step", "model.eva_summarise_ms_per_step",
             "kernel.flash_eva_fwd_roofline", "kernel.flash_eva_bwd_roofline")
    for name in names:
        assert readers[name].read(reading) is None
        assert readers[name].APPLIES == {"runner": "lm_train_eva"}
        assert readers[name].MOVES == "tokens_per_chip_s"
    from benchmarks.trace import reduce as R
    empty = R.Trace(devices={"/device:TPU:0": R.DeviceLines(
        ops=[R.Event("fusion.1", 0.0, 1e6)], modules=[])},
        host=[R.Event(R.WINDOW_SPAN, 0.0, 2e6)])
    assert eva.summarize(empty, _fake_trace()[1]) is None


def test_the_benchmark_declares_the_cell_and_its_metrics():
    from benchmarks import harness

    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = harness.load_cell(CELL)
    assert cell.chips == 1 and cell.runner == "lm_train_eva"
    assert cell.traffic["seq"] == 16384 and cell.traffic["batch"] == 1
    assert cell.traffic["check"] == {"batch": 1, "seq": 8192}
    assert cell.traffic["generator"] == "zipf_tokens"
    names = {m["name"] for m in cell.per_layer}
    assert {"model.attn_eva_ms_per_step", "model.eva_summarise_ms_per_step",
            "kernel.flash_eva_fwd_roofline", "kernel.flash_eva_bwd_roofline",
            "model.mfu_pct", "device.idle_share", "model.matmul_share",
            "model.head_loss_ms_per_step", "updater.ms_per_step",
            "compile.in_window", "startup.draw_s"} <= names
    assert not names & {"kernel.flash_fwd_roofline", "kernel.flash_share",
                        "kernel.flash_bwd_roofline", "model.moe_share",
                        "kernel.kda_fwd_roofline", "startup.settle_s",
                        "model.attn_linear_ms_per_step"}
    assert {m["name"] for m in cell.end_to_end} == {
        "setup_s", "tokens_per_chip_s", "peak_hbm_gib"}
    readers = harness.layer_readers(cell.search)
    assert names <= set(readers)
    mine = [w for w in bench["workloads"] if w["config"] == "evabyte-6.5b-l4"]
    assert [w["name"] for w in mine] == [CELL]
    assert bench["workloads"][8]["name"] == CELL
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    mine = [m for m in bench["per_layer"] if "eva" in m["name"]]
    assert len(mine) == 4
    for m in mine:
        assert m["workloads"] == [CELL] and m["moves"] == "tokens_per_chip_s"
    for w in bench["workloads"] + bench["configs"]:
        assert len(w["why"]) <= 200
