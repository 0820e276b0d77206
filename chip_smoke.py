#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, no arguments: ``python chip_smoke.py`` from the repo root, on
a machine with a TPU (the chip tool runs it there).  It drives the main
path once through the entry points a user calls — tables → updaters →
fused app steps → ``TransformerTrainer`` → the Pallas flash kernels — at
the full width and depth of the flagship configuration, then a 2-layer,
8-expert routed model at OLMoE's widths through the grouped (dropless)
schedule, with random weights from a seed, and checks what comes out by
the repo's own means.

It refuses to run (non-zero exit, no result line) unless JAX's first
device is a TPU, and when ``MVTPU_NO_FLASH``/``MVTPU_FORCE_FLASH`` would
swap the attention body under it.  Any failed check raises: there is no
``try/except`` that carries on, so a failed phase can never end in exit 0.
The last two lines of stdout are one JSON object each: the report of
every phase, ending ``"claim": null`` (no number printed here is a claim),
and then, last, the verdict with exactly these keys, the device as JAX
reports it::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": n}}

The sizes below are never shrunk by the script; if the flagship batch
stops fitting, that is a finding for whoever changed the program.
"""

from __future__ import annotations

import json
import os
import re
import sys
import time

import numpy as np

# BENCHMARK.json's ouro-2.6b-l16-ut1 in width, depth, scan and remat, with an
# invented vocabulary of 32,768 (the configuration's is 49,152); 4 x 2048 is
# its seq2k-b4 traffic.
FLAGSHIP = dict(vocab_size=32768, dim=2048, n_layers=16, n_heads=16,
                hidden=5632, max_seq=2048, scan_layers=True, remat=True,
                remat_policy="dots")
FLAGSHIP_BATCH, FLAGSHIP_SEQ = 4, 2048
# The routed FFN on the normal path at OLMoE-1B-7B's widths (2048, 16 x 128,
# experts of 1024, QK-norm, unnormalised top-k, z-loss), with 8 experts and
# 2 layers so that the phase is seconds: the grouped (dropless) schedule.
MOE = dict(vocab_size=32768, dim=2048, n_layers=2, n_heads=16, hidden=1024,
           max_seq=2048, num_experts=8, top_k=2, norm_topk_prob=False,
           qk_norm=True, router_z_loss_coef=0.001, moe_dispatch="grouped",
           scan_layers=True, remat=True, remat_policy="dots")
MOE_BATCH, MOE_SEQ = 2, 2048
# The 784 x 10 LR and the 100,000 x 128 word2vec tables of BASELINE.md's
# native fleets, at one 8192 batch.
LR_SHAPE = dict(batch=8192, features=784, classes=10)
W2V_SHAPE = dict(batch=8192, vocab=100_000, dim=128, negatives=5)
# The published width (GoogleNews-vectors-negative300): not a multiple of the
# 128 lanes, so the backend's default layout for the bare shape is not
# row-major and the table stores its rows padded (docs/embedding.md "Resting
# layout").
W2V_PUBLISHED_WIDTH = dict(W2V_SHAPE, dim=300)
# bf16 compute, f32 loss: the same step on another layout re-orders the
# reductions, nothing more.
LOSS_RTOL = 2e-2


def require(cond: bool, msg: str) -> None:
    """A check that survives ``python -O`` (``assert`` does not)."""
    if not cond:
        raise RuntimeError(f"chip_smoke check failed: {msg}")


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check_device() -> dict:
    """Refuse anything but a TPU, before anything compiles."""
    for var in ("MVTPU_NO_FLASH", "MVTPU_FORCE_FLASH"):
        if os.environ.get(var):
            sys.exit(f"chip_smoke: refusing to run with {var} set — it "
                     f"swaps the attention body this smoke must exercise")
    import jax
    import jaxlib

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: refusing to run: JAX's first device is on "
                 f"platform '{dev.platform}', need 'tpu'")
    from importlib import metadata

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    say(f"device {device}  jax {jax.__version__}  jaxlib "
        f"{jaxlib.__version__}  libtpu {metadata.version('libtpu')}")
    return device


def jnp_traces() -> int:
    """Attention traces that took the O(T^2) jnp body so far
    (``parallel/ring_attention.py:_flash_dispatch`` counts them)."""
    from multiverso_tpu import metrics

    return int(metrics.counter("attention.traced", {"path": "jnp"}).value)


def check_losses(name: str, losses) -> None:
    require(all(np.isfinite(losses)), f"{name}: non-finite loss {losses}")
    require(losses[-1] < losses[0],
            f"{name}: loss did not fall: {losses[0]} -> {losses[-1]}")


# --------------------------------------------------------------- paper surface
def phase_tables(mv, size: int = 1 << 20, rows: int = 4096,
                 cols: int = 128) -> dict:
    """ArrayTable device add/get and MatrixTable row add/get, exact values
    (lr 0.5 and deltas in {1, 2}: every product is exact in float32)."""
    import jax
    import jax.numpy as jnp

    n_dev = jax.device_count()
    half = mv.AddOption(learning_rate=0.5)
    t = mv.ArrayTable(size, name="smoke_array")
    require(len(t.raw_value()[0].sharding.device_set) == n_dev,
            f"ArrayTable spans {len(t.raw_value()[0].sharding.device_set)} "
            f"of {n_dev} devices")
    t.add(jnp.full((size,), 2.0, jnp.float32), option=half)
    t.add(jnp.ones((size,), jnp.float32), option=half, sync=True)
    got = t.get(device=True)
    require(isinstance(got, jax.Array) and got.shape == (size,),
            f"get(device=True) returned {type(got)} {got.shape}")
    require(bool(jnp.all(got == -1.5)), "ArrayTable sgd: expected -1.5")
    require(bool(np.all(t.get() == -1.5)), "ArrayTable host get != -1.5")

    m = mv.MatrixTable(rows, cols, name="smoke_matrix")
    require(len(m.raw_value()[0].sharding.device_set) == n_dev,
            "MatrixTable does not span every device")
    ids = np.array([3, 7, rows - 1, 3], np.int32)      # row 3 twice: sums
    m.add_rows(ids, np.full((ids.size, cols), 2.0, np.float32),
               option=half, sync=True)
    got = m.get_rows(np.array([3, 7, rows - 1, 5], np.int32))
    want = np.array([-2.0, -1.0, -1.0, 0.0], np.float32)[:, None]
    require(got.shape == (4, cols) and bool(np.all(got == want)),
            f"MatrixTable rows: got {got[:, 0]}, want {want[:, 0]}")
    return {"array_size": size, "matrix_shape": [rows, cols]}


def phase_bsp(mv, size: int = 1024) -> dict:
    """One BSP table: adds invisible before the barrier, visible after."""
    t = mv.ArrayTable(size, sync=True, name="smoke_bsp")
    t.add(np.ones(size, np.float32), option=mv.AddOption(learning_rate=0.5))
    require(bool(np.all(t.get() == 0.0)), "BSP add visible before barrier")
    mv.barrier()
    require(bool(np.all(t.get() == -0.5)), "BSP add missing after barrier")
    return {"size": size}


def phase_lr(mv, batch: int, features: int, classes: int,
             steps: int = 40) -> dict:
    """The fused LR step: loss must fall."""
    from multiverso_tpu.apps import (LogisticRegression,
                                     synthetic_classification)

    require(batch % mv.num_replicas() == 0,
            f"LR batch {batch} does not divide {mv.num_replicas()} replicas")
    x, y = synthetic_classification(batch, features, classes, seed=0)
    lr = LogisticRegression(features, classes, learning_rate=0.1,
                            name="smoke_lr")
    step, place = lr.make_fused_step()
    data, state = lr.table.raw_value()
    xb, yb = place(x), place(y)
    losses = []
    for _ in range(steps):
        data, state, loss = step(data, state, xb, yb)
        losses.append(loss)
    lr.table.raw_assign(data, state)
    losses = [float(v) for v in losses]
    check_losses("lr", losses)
    return {"steps": steps, "loss_first": losses[0], "loss_last": losses[-1]}


def phase_w2v(mv, batch: int, vocab: int, dim: int, negatives: int,
              steps: int = 30) -> dict:
    """The fused word2vec step.

    ``_sgns_loss`` is a batch MEAN, so at the paper's lr 0.025 one step
    moves each touched row by lr/batch of its gradient and the float32
    loss does not change in thirty steps.  The smoke steps at
    lr = 0.025 * batch — the per-pair step of the reference's per-sample
    SGD — so that "falling" is observable.

    The tables rest row-major whatever the width (on the chip, as the
    buffers report it), no step after the second compiles, the padding of
    a row stays zero, and one table goes through a checkpoint and comes
    back equal."""
    import jax

    from multiverso_tpu.apps import SkipGram

    require(batch % mv.num_replicas() == 0,
            f"w2v batch {batch} does not divide {mv.num_replicas()} replicas")
    rng = np.random.RandomState(0)
    c = rng.randint(vocab, size=batch).astype(np.int32)
    o = rng.randint(vocab, size=batch).astype(np.int32)
    neg = rng.randint(vocab, size=(batch, negatives)).astype(np.int32)
    sg = SkipGram(vocab, dim, negatives=negatives,
                  learning_rate=0.025 * batch, name=f"smoke_w2v{dim}")
    require(len(sg.table_in.raw_value()[0].sharding.device_set)
            == mv.get_context().mesh.size,
            "w2v table does not span every device")

    def check_resting(when: str) -> None:
        for t in (sg.table_in, sg.table_out):
            buf = t.raw_value()[0]
            require(buf.shape[1] == t.stored_cols >= dim,
                    f"{t.name} {when}: buffer {buf.shape}, table stores "
                    f"{t.stored_cols} columns")
            require(jax.default_backend() != "tpu"
                    or buf.format.layout.major_to_minor == (0, 1),
                    f"{t.name} {when}: rests in {buf.format.layout}")
            require(not bool(buf[:, dim:].any()),
                    f"{t.name} {when}: the padding of a row is not zero")

    check_resting("after construction")
    step, place = sg.make_fused_step()
    din, sin = sg.table_in.raw_value()
    dout, sout = sg.table_out.raw_value()
    cb, ob, negb = place(c), place(o), place(neg)
    losses, programs = [], []
    for _ in range(steps):
        din, sin, dout, sout, loss = step(din, sin, dout, sout, cb, ob, negb)
        losses.append(loss)
        programs.append(step._cache_size())
    sg.table_in.raw_assign(din, sin)
    sg.table_out.raw_assign(dout, sout)
    losses = [float(v) for v in losses]
    check_losses("w2v", losses)
    check_resting(f"after {steps} steps")
    # The second call may meet the first one's outputs under another
    # spelling of the same sharding; from then on nothing compiles.
    require(programs[-1] == programs[min(1, steps - 1)] <= 2,
            f"the w2v step kept compiling: {programs}")
    rows = np.unique(c)[:64]
    before = sg.table_in.get_rows(rows)
    snap = sg.table_in.store_state()
    sg.table_in.add_rows(rows, np.ones((rows.size, dim), np.float32),
                         sync=True)
    require(not np.array_equal(sg.table_in.get_rows(rows), before),
            "add_rows left the rows as they were")
    sg.table_in.load_state(snap)
    require(np.array_equal(sg.table_in.get_rows(rows), before),
            "a stored and loaded table reads other rows")
    check_resting("after add_rows and load_state")
    return {"steps": steps, "loss_first": losses[0], "loss_last": losses[-1],
            "dim": dim, "stored_cols": sg.table_in.stored_cols}


def phase_paper_surface() -> dict:
    """init → tables → fused app steps → BSP barrier → shutdown."""
    import multiverso_tpu as mv

    mv.init(args=["-updater_type=sgd", "-sync=false"])
    out = {"tables": phase_tables(mv), "lr": phase_lr(mv, **LR_SHAPE),
           "w2v": phase_w2v(mv, **W2V_SHAPE),
           "w2v_published_width": phase_w2v(mv, **W2V_PUBLISHED_WIDTH),
           "bsp": phase_bsp(mv)}
    mv.shutdown()
    return out


# ------------------------------------------------------------ kernel numerics
def phase_flash_reference(batch: int = 2, heads: int = 4, seq: int = 1024,
                          head_dim: int = 128, kv_heads: int | None = None,
                          window: int | None = None,
                          tol: float = 3e-2) -> dict:
    """The attention the trainer dispatches to (on the chip: the compiled
    forward kernel and the one backward call, ``flash_bwd`` or with a
    ``window`` ``flash_win_bwd``) against a dense float32 reference computed
    a head at a time, so that a cell's own shape fits (1 x 16 x 8192;
    Laguna's 72 query over 8 K/V heads with window 512).  Errors are
    relative to the reference's largest entry; bf16 rounding of p and of the
    outputs is ~2^-8 of that."""
    import jax
    import jax.numpy as jnp

    from multiverso_tpu import metrics
    from multiverso_tpu.parallel.ring_attention import (
        blockwise_attention_local)

    rng = np.random.RandomState(0)
    kv_heads = kv_heads or heads
    group = heads // kv_heads

    def draw(h):
        return jnp.asarray(0.5 * rng.randn(batch, h, seq, head_dim),
                           jnp.bfloat16)

    q, k, v, w = draw(heads), draw(kv_heads), draw(kv_heads), draw(heads)
    scale = head_dim ** -0.5
    t = jnp.arange(seq)
    visible = t[:, None] >= t[None, :]
    if window is not None:
        visible = visible & (t[None, :] > t[:, None] - window)

    def dense(q, k, v):
        hi = jax.lax.Precision.HIGHEST
        q, k, v = (x.astype(jnp.float32) for x in (q, k, v))

        @jax.checkpoint
        def one(i):                  # a (batch, query head) pair: [T, D]
            b, h = i // heads, i % heads
            s = jnp.dot(q[b, h], k[b, h // group].T, precision=hi) * scale
            return jnp.dot(jax.nn.softmax(jnp.where(visible, s, -jnp.inf),
                                          -1), v[b, h // group],
                           precision=hi)

        return jax.lax.map(one, jnp.arange(batch * heads)).reshape(q.shape)

    def kernel(q, k, v):
        return blockwise_attention_local(q, k, v, scale, causal=True,
                                         window=window)

    def out_and_grads(attn):
        def loss(q, k, v):
            o = attn(q, k, v).astype(jnp.float32)
            return jnp.sum(o * w.astype(jnp.float32)), o
        (_, o), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
        return (o,) + grads

    def bwd_traces():
        return [metrics.counter("attention.bwd_traced", {"path": p}).value
                for p in ("fused", "split")]

    before, bwd_before = jnp_traces(), bwd_traces()
    got_all = out_and_grads(kernel)
    require(jnp_traces() == before,
            "the dispatcher sent the reference check to the jnp body")
    fused, split = (a - b for a, b in zip(bwd_traces(), bwd_before))
    require(fused == 1 and split == 0,
            f"the backward of {(batch, heads, seq, head_dim)} was traced "
            f"fused {fused} and split {split} times, not once fused")
    errs = {}
    for name, got, want in zip(("o", "dq", "dk", "dv"),
                               got_all, out_and_grads(dense)):
        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        require(got.shape == want.shape and bool(np.all(np.isfinite(got))),
                f"flash {name}: shape {got.shape} or non-finite values")
        errs[name] = float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
        require(errs[name] <= tol,
                f"flash {name} differs from the dense f32 reference by "
                f"{errs[name]:.4f} of its largest entry (tol {tol})")
    return {"shape": [batch, heads, kv_heads, seq, head_dim],
            "window": window, "max_rel_err": errs}


def phase_flash_latent(heads: int = 32, seq: int = 8192, nope: int = 128,
                       rope: int = 64, v_dim: int = 128,
                       tol: float = 3e-2) -> dict:
    """The two-width kernels (``flash_mla_fwd`` and the one backward call
    ``flash_mla_bwd``, which this shape takes) at the shape of the cell
    ``xing4.0-29b-a4b-e8.zipf-seq8k-b1`` (1 x 32 x 8192, scores 128 + 64
    wide, values 128, the rotated key one head for all 32) against a dense
    float32 reference computed a head at a time.  Errors as in
    ``phase_flash_reference``."""
    import jax
    import jax.numpy as jnp

    from multiverso_tpu.parallel.ring_attention import (
        blockwise_attention_local)

    rng = np.random.RandomState(1)

    def draw(h, width):
        return jnp.asarray(0.5 * rng.randn(1, h, seq, width), jnp.bfloat16)

    args = (draw(heads, nope), draw(heads, rope), draw(heads, nope),
            draw(1, rope), draw(heads, v_dim))
    w = draw(heads, v_dim)
    scale = (nope + rope) ** -0.5
    mask = jnp.tril(jnp.ones((seq, seq), bool))

    def dense(qn, qr, kn, kr, v):
        hi = jax.lax.Precision.HIGHEST
        qn, qr, kn, kr, v = (x.astype(jnp.float32)
                             for x in (qn, qr, kn, kr, v))

        @jax.checkpoint
        def one(head):                      # [T, width] each
            qn, qr, kn, v = head
            s = (jnp.dot(qn, kn.T, precision=hi)
                 + jnp.dot(qr, kr[0, 0].T, precision=hi)) * scale
            return jnp.dot(jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1),
                           v, precision=hi)

        return jax.lax.map(one, (qn[0], qr[0], kn[0], v[0]))[None]

    def kernel(qn, qr, kn, kr, v):
        return blockwise_attention_local(qn, kn, v, scale, causal=True,
                                         q_rope=qr, k_rope=kr)

    def out_and_grads(attn):
        def loss(*a):
            o = attn(*a).astype(jnp.float32)
            return jnp.sum(o * w.astype(jnp.float32)), o
        (_, o), grads = jax.jit(jax.value_and_grad(
            loss, argnums=tuple(range(5)), has_aux=True))(*args)
        return (o,) + grads

    before = jnp_traces()
    got_all = out_and_grads(kernel)
    require(jnp_traces() == before,
            "the dispatcher sent the two-width check to the jnp body")
    errs = {}
    for name, got, want in zip(("o", "dq_nope", "dq_rope", "dk_nope",
                                "dk_rope", "dv"),
                               got_all, out_and_grads(dense)):
        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        require(got.shape == want.shape and bool(np.all(np.isfinite(got))),
                f"flash_mla {name}: shape {got.shape} or non-finite values")
        errs[name] = float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
        require(errs[name] <= tol,
                f"flash_mla {name} differs from the dense f32 reference by "
                f"{errs[name]:.4f} of its largest entry (tol {tol})")
    return {"shape": [1, heads, seq, nope + rope, v_dim],
            "max_rel_err": errs}


# -------------------------------------------------------------------- flagship
def held_kernels(text: str, cfg, batch: int, seq: int, mesh_shape) -> dict:
    """Which attention body a lowered step holds, read from its text.

    With ``remat_policy="dots"`` saving the kernel's (o, lse), the scanned
    layer's grad holds exactly two Mosaic kernels: the forward and the one
    backward call (``flash_bwd``: dq, dk and dv together).
    The jnp body's mark is a [batch, heads, T, T] score tensor, at global
    or per-shard sizes (a ring piece is T/sp or T/2sp long)."""
    dp, sp, tp = (int(mesh_shape.get(a, 1)) for a in ("dp", "sp", "tp"))
    batches = {batch, batch // dp}
    heads = {cfg.n_heads, cfg.n_heads // tp}
    lengths = {seq, seq // sp, seq // (2 * sp)}
    scores = sorted({
        m.group(0)
        for m in re.finditer(r"tensor<(\d+)x(\d+)x(\d+)x(\d+)x\w+>", text)
        if int(m.group(1)) in batches and int(m.group(2)) in heads
        and int(m.group(3)) in lengths and int(m.group(4)) in lengths})
    return {"tpu_custom_call": text.count("tpu_custom_call"),
            "score_tensors": scores}


def phase_flagship(cfg, batch: int, seq: int, mesh, steps: int = 4,
                   kernels_per_step: int = 2) -> dict:
    """``steps`` train steps of ``cfg`` on one repeated batch over ``mesh``.

    Returns the losses, the compile and per-step seconds, the per-device
    memory the backend reports, and what the lowered step holds.
    ``kernels_per_step``: Mosaic custom calls expected in the lowered step
    (the forward and the backward of the scanned layer; a ring over sp holds
    more)."""
    import jax

    from multiverso_tpu.models import TransformerTrainer

    dp = int(mesh.shape.get("dp", 1))
    require(batch % dp == 0,
            f"flagship batch {batch} does not divide dp={dp}: the smoke "
            f"does not replicate a batch")
    t0 = time.perf_counter()
    tr = TransformerTrainer(cfg, mesh, updater_type="sgd")
    jax.block_until_ready(tr.params)
    init_s = time.perf_counter() - t0
    n_dev = mesh.size
    for path, leaf in jax.tree_util.tree_flatten_with_path(tr.params)[0]:
        require(len(leaf.sharding.device_set) == n_dev,
                f"param {jax.tree_util.keystr(path)} lives on "
                f"{len(leaf.sharding.device_set)} of {n_dev} devices")
    toks = np.random.RandomState(0).randint(
        cfg.vocab_size, size=(batch, seq)).astype(np.int32)

    jnp_before = jnp_traces()
    lowered = tr.lowered_step(toks)
    held = held_kernels(lowered.as_text(), cfg, batch, seq, mesh.shape)
    held["jnp_traces"] = jnp_traces() - jnp_before
    require(held["tpu_custom_call"] == kernels_per_step,
            f"lowered step holds {held['tpu_custom_call']} Mosaic custom "
            f"calls, expected {kernels_per_step} (fwd and bwd a piece)")
    require(held["jnp_traces"] == 0 and not held["score_tensors"],
            f"lowered step holds the O(T^2) jnp attention body: {held}")

    t0 = time.perf_counter()
    compiled = lowered.compile()
    compile_s = time.perf_counter() - t0
    # The compiler's own account of the step, beside memory_stats():
    # on this runtime the latter does not count a program's temporaries.
    mem = compiled.memory_analysis()
    compiled_bytes = {k: int(getattr(mem, f"{k}_size_in_bytes"))
                      for k in ("argument", "output", "alias", "temp")}

    # The user's entry point.  Its first call finds the program the line
    # above compiled in the persistent cache (compile_cache.configure).
    losses, step_s = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        losses.append(tr.train_step(toks))     # float(): waits for the chip
        step_s.append(time.perf_counter() - t0)
    check_losses(f"flagship {dict(mesh.shape)}", losses)

    # The CPU backend (the tests' mesh) reports no memory statistics.
    stats = [d.memory_stats() for d in mesh.devices.flat]
    if mesh.devices.flat[0].platform == "tpu":
        require(all(stats), "the TPU backend reported no memory_stats()")
    memory = {}
    if all(stats):
        in_use = [s["bytes_in_use"] for s in stats]
        require(max(in_use) <= 2 * min(in_use),
                f"per-device bytes_in_use differ by more than 2x: {in_use}")
        memory = {
            "peak_bytes_in_use": max(s["peak_bytes_in_use"] for s in stats),
            "bytes_limit": stats[0]["bytes_limit"],
            "bytes_in_use_per_device": in_use,
        }
    del tr
    return {
        "mesh": {k: int(v) for k, v in mesh.shape.items()},
        "batch": batch, "seq": seq, "steps": steps, "losses": losses,
        "init_s": round(init_s, 2), "compile_s": round(compile_s, 2),
        "first_step_s": round(step_s[0], 3),
        "step_s": round(float(np.median(step_s[1:])), 4),
        "compiled_bytes": compiled_bytes, **memory,
        "kernels": held,
    }


def phase_moe(cfg, batch: int, seq: int, mesh, steps: int = 3,
              kernels_per_step: int = 2) -> dict:
    """The grouped expert schedule through ``TransformerTrainer``: steps
    that fall, a step-0 loss equal to the ``dense`` schedule's (every
    expert on every token: the oracle) within bf16 tolerance, and how
    unevenly the routes of this batch fall on the experts."""
    import dataclasses

    from multiverso_tpu.models import init_params
    from multiverso_tpu.models.transformer import expert_load

    require(cfg.moe_dispatch == "grouped", "phase_moe wants the grouped "
            f"schedule, got {cfg.moe_dispatch!r}")
    res = phase_flagship(cfg, batch, seq, mesh, steps=steps,
                         kernels_per_step=kernels_per_step)
    dense = phase_flagship(dataclasses.replace(cfg, moe_dispatch="dense"),
                           batch, seq, mesh, steps=2,
                           kernels_per_step=kernels_per_step)
    first, oracle = res["losses"][0], dense["losses"][0]
    require(abs(first - oracle) <= LOSS_RTOL * abs(oracle),
            f"grouped step-0 loss {first} vs dense dispatch {oracle}")
    toks = np.random.RandomState(0).randint(
        cfg.vocab_size, size=(batch, seq)).astype(np.int32)
    params = init_params(cfg, seed=0)
    load = np.asarray(expert_load(params, toks, cfg))
    require(load.shape == (cfg.n_layers, cfg.num_experts)
            and (load.sum(axis=1) == batch * seq * cfg.top_k).all(),
            f"expert_load lost or invented routes: {load.tolist()}")
    res["dense_dispatch_loss"] = oracle
    res["expert_load_max_over_mean"] = [
        round(float(row.max() / row.mean()), 3) for row in load]
    return res


def phase_multichip(cfg, batch: int, seq: int, ref_loss: float,
                    kernels=(2, 10)) -> dict:
    """The flagship on ("dp",)=4 and on ("dp","sp","tp")=(1,2,2): same
    batch, step-0 loss equal to the one-chip value within bf16 tolerance.

    ``kernels``: Mosaic custom calls each layout's step holds.  (1,2,2)
    is a zigzag ring over sp=2: the self step runs three aligned pieces,
    the low and the high step one each; every piece is a forward kernel
    plus one backward kernel in the grad: ten."""
    import jax
    from jax.sharding import Mesh

    devs = np.asarray(jax.devices()[:4])
    out = {}
    for name, mesh, n_kernels in (
            ("dp4", Mesh(devs, ("dp",)), kernels[0]),
            ("dp1_sp2_tp2", Mesh(devs.reshape(1, 2, 2),
                                 ("dp", "sp", "tp")), kernels[1])):
        res = phase_flagship(cfg, batch, seq, mesh, steps=3,
                             kernels_per_step=n_kernels)
        require(abs(res["losses"][0] - ref_loss) <= LOSS_RTOL * abs(ref_loss),
                f"{name}: step-0 loss {res['losses'][0]} vs one-chip "
                f"{ref_loss}")
        say(f"multichip {name}: {res}")
        out[name] = res
    return out


def main() -> int:
    t_start = time.perf_counter()
    device = check_device()

    import jax
    from jax.sharding import Mesh

    import multiverso_tpu as mv
    from multiverso_tpu.models import TransformerConfig

    result = {"device": device}
    result["paper_surface"] = phase_paper_surface()
    say(f"paper surface: {result['paper_surface']}")
    # mv.init() placed it (multiverso_tpu/compile_cache.py).
    result["compile_cache_dir"] = jax.config.jax_compilation_cache_dir

    result["flash_reference"] = phase_flash_reference()
    say(f"flash vs dense f32 reference: {result['flash_reference']}")
    # the backward at two cells' own shapes: seq8k-b1's, Laguna's sliding
    result["flash_reference_8k"] = phase_flash_reference(
        batch=1, heads=16, seq=8192)
    say(f"flash at 1 x 16 x 8192: {result['flash_reference_8k']}")
    result["flash_reference_sliding"] = phase_flash_reference(
        batch=1, heads=72, seq=8192, kv_heads=8, window=512)
    say(f"flash at 72 / 8 heads, window 512: "
        f"{result['flash_reference_sliding']}")
    result["flash_latent"] = phase_flash_latent()
    say(f"two-width flash vs dense f32 reference: {result['flash_latent']}")

    # One chip first, whatever the host holds: its step-0 loss is what the
    # four-chip layouts are held to.
    cfg = TransformerConfig(**FLAGSHIP)
    one = Mesh(np.asarray(jax.devices()[:1]), ("dp",))
    result["flagship"] = phase_flagship(cfg, FLAGSHIP_BATCH, FLAGSHIP_SEQ,
                                        one)
    say(f"flagship: {result['flagship']}")

    result["moe"] = phase_moe(TransformerConfig(**MOE), MOE_BATCH, MOE_SEQ,
                              one)
    say(f"moe (grouped, {MOE['num_experts']} experts): {result['moe']}")

    if jax.device_count() >= 4:
        result["multichip"] = phase_multichip(
            cfg, FLAGSHIP_BATCH, FLAGSHIP_SEQ,
            result["flagship"]["losses"][0])
    else:
        result["multichip"] = f"skipped, {jax.device_count()} chip"
        say(f"multichip: {result['multichip']}")
    require(not mv.initialized(), "runtime still initialized at exit")

    result["wall_s"] = round(time.perf_counter() - t_start, 1)
    result["claim"] = None
    print(json.dumps(result), flush=True)
    # The verdict: these keys and no others, and nothing printed after it.
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
