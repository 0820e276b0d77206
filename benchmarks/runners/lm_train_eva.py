"""Runner ``lm_train_eva``: language-model training through
``multiverso_tpu.models.TransformerTrainer`` for a configuration whose layers
run EVA attention (one softmax over a query's own window and chunk summaries
of every earlier window, ``ops/flash_eva.py``), with a float32 residual, norm
gains stored as offsets from one, and ``n_pred_heads`` parallel heads.

``lm_train`` takes no such configuration without an edit (it counts dense
causal attention, wants ``attention.traced`` and samples a ``wq`` and a ``w2``
alone), so this is its sibling: the same set-up and the same window loop
(``lm_train``'s docstring: trainer, reference check through a step of the
sample's shape, the cell's step compiled with its memory account, two warm-up
steps on one batch, then steps enqueued one ahead on fresh seeded batches, the
rate from the median time between completions; no settling), the same
``correct`` checks, and its own:

- published keys held equal to the ``model`` group (``_check_published``);
- **the attention alone** (``attention_check``): ``ops/flash_eva.py``'s
  summariser and kernels, both passes (bfloat16 operands on both sides),
  against the reference's dense masked softmax: the output and the five
  gradients (q, k, v, ``phi``, ``mu``), before four layers of bfloat16
  matmuls cover what narrower softmax statistics move.  On two sets of
  inputs of the check's length: ``layer_inputs``, the q, k, v the timed
  model's last layer hands its attention at the check batch with that
  layer's own ``phi`` and ``mu`` (the reference's forward pass on the
  program's weights), and ``scaled_inputs``, seeded normals 1.5 wide, whose
  scores are a few units so that rounding them shows (``evabyte_lm.py``'s
  docstring: which set refuses which control);
- **the step's own loss and gradients** at the check's shape (1 x 8,192: four
  windows, the last of which sees 384 summaries) against
  ``benchmarks/reference/evabyte_lm.py`` on the program's own weights
  (``reference_side`` / ``program_side`` / ``compare``): the loss; the float32
  logits at ``LOGIT_ROWS`` positions spread over the sequence (the forward
  pass alone), held by the median of the rows' distances; the gradient of
  EVERY leaf (the matrices by a ``SAMPLE_ROWS`` tile, the embedding, gains,
  ``phi`` and ``mu`` whole), ``phi`` and ``mu`` in a class of their own: they
  learn through the summaries alone, so their gradients agreeing is the proof
  that the staircase and its backward are right.  The gradients are those of
  ``lm_loss`` under the trainer's configuration, the function
  ``TransformerTrainer._raw_step`` differentiates;
- **the timed program's own step applies those gradients** (``step_side`` /
  ``step_compare``): one ``trainer.train_step_async`` at the check's shape,
  and every sampled leaf after it against ``old - lr * gradient`` in the
  updater's own float32 arithmetic, the gradient the one just held to the
  reference.  A leaf's reading is ``|new - wanted| / |old - wanted|``: 0 for
  the wanted step to the bit, 1 for a leaf the step left as it was, 2 for a
  step the wrong way; what the two programs' gradients differ by (a float32
  spacing here and there) is all a sound step reads.  Taken this way and not
  as ``(old - new) / lr`` against the reference, as the other runners take
  it, because many a leaf's step is near float32's spacing of the parameter
  (821 M parameters under a loss over 320 ids), so that quotient would read
  the spacing and not the program; a leaf whose wanted step rounds to nothing
  everywhere is named (``unresolved``) and fails the check, for nothing
  could then be said of it;
- **the stream and the logits float32 in the timed step's own text**
  (``stream_dtypes``): a bfloat16 residual reads within a fifth of what the
  program's own bfloat16 operands cost, on every leaf and on the logits, and
  no limit stands between the two with room (``evabyte_lm.py``'s docstring),
  so that guarantee is held where it can be read exactly, by a walk of the
  lowered step: the layers' scan carries ``[batch, seq, dim]`` in float32 and
  in nothing else; every float32 sum into that shape has an operand that was
  not just converted from bfloat16 (the stream itself: a function's argument
  or an earlier sum), and no function hands back a float32 ``[batch, seq,
  dim]`` just converted from bfloat16, so the stream is nowhere rounded on
  its way from layer to layer (the norm's read is a convert the stream does
  not follow); its logits are float32;
- the loss lower after the two warm-up steps on one batch than before them;
- the attention traced through its kernels, both passes
  (``attention.eva_traced`` / ``attention.eva_bwd_traced{window=,chunk=,
  path=mosaic|interpret}``, none with ``path=jnp``);
- facts from ``benchmarks/flops_eva.py``.

**What the check refuses** is shown by ``controls``: the reference computed in
a precision below the one the configuration states (``CONTROLS``: the
program's bfloat16 operands AND one guarantee dropped) stands in the program's
place and goes through ``attention_compare`` / ``compare``; for the step, a
leaf left as it was and a step the wrong way go through ``step_compare``
(``step_controls``).  ``python -m benchmarks.runners.lm_train_eva --seeds a,b``
prints the program's readings and the controls' a seed, on the chip
(``--controls 1``: the whole model in a lower precision too, four more passes
of the reference).
"""

from __future__ import annotations

import sys
import time

import numpy as np

from benchmarks import flops_eva
from benchmarks.harness import (Measured, compared, compiled_peak_bytes,
                                load_module)
from benchmarks.runners.lm_train import (LOSSES_LOGGED, SAMPLE_ROWS,
                                         step_seconds)

# Published config keys and the program's field for each.
PUBLISHED = {"hidden_size": "dim", "num_attention_heads": "n_heads",
             "intermediate_size": "hidden", "vocab_size": "vocab_size",
             "num_hidden_layers": "n_layers", "rope_theta": "rope_theta",
             "rms_norm_eps": "norm_eps", "max_position_embeddings": "max_seq",
             "max_seq_length": "max_seq", "window_size": "eva_window",
             "chunk_size": "eva_chunk", "num_pred_heads": "n_pred_heads",
             "norm_add_unit_offset": "norm_unit_offset",
             "init_std": "init_std"}
LOGIT_ROWS = 16
MATRICES = ("wq", "wk", "wv", "wo", "w1", "w3", "w2")
VECTORS = ("attn_norm", "mlp_norm", "phi", "mu")
POOL = ("phi", "mu")
ATTN_INPUTS = ("q", "k", "v", "phi", "mu")
# The reference in a precision below the stated one: the program's bfloat16
# operands (``compute``: alone no fault) and one guarantee dropped.
CONTROLS = {"compute_bf16": {"compute": "bfloat16"},
            "residual_bf16": {"compute": "bfloat16", "residual": "bfloat16"},
            "stats_bf16": {"compute": "bfloat16", "stats": "bfloat16"},
            "logits_bf16": {"compute": "bfloat16", "logits": "bfloat16"}}
# (reading, its limit) of the reference check, for the result line
COMPARED = (("loss_abs_err", "loss_atol"), ("median_logits", "logits_rtol"),
            ("worst", "grad_rtol"), ("worst_pool", "grad_rtol_pool"),
            ("step.worst", "step.rtol"),
            ("attention.out_rel_err", "attention.out_rtol"),
            ("attention.worst_grad", "attention.grad_rtol"),
            ("attention.dphi", "attention.phi_rtol"),
            ("attention_scaled.out_rel_err", "attention_scaled.out_rtol"),
            ("attention_scaled.worst_grad", "attention_scaled.grad_rtol"))


def _check_published(config: dict) -> None:
    model, name = config["model"], config["name"]

    def same(what, published, run):
        if published != run:
            raise ValueError(f"{name}: {what}={published!r} but the model "
                             f"group runs {run!r}")

    for key, fld in PUBLISHED.items():
        same(key, config[key], model[fld])
    heads = config["num_attention_heads"]
    same("num_key_value_heads", config["num_key_value_heads"], heads)
    same("head_dim", config["hidden_size"] // heads, model["head_dim"])
    same("attention_class", config["attention_class"], "eva")
    same("layer_types", ["eva_attention"] * config["num_hidden_layers"],
         list(model["layer_types"]))
    same("fp32_skip_add", config["fp32_skip_add"],
         model["residual_dtype"] == "float32")
    same("fp32_logits", config["fp32_logits"],
         model["logits_dtype"] == "float32")
    for key, run in (("attention_bias", False), ("fp32_ln", False),
                     ("mixedp_attn", True),
                     ("hidden_act", "silu"), ("rope_scaling", None),
                     ("tie_word_embeddings", False)):
        same(key, config[key], run)


# --------------------------------------------------------- one train step
def _pick(leaf, top, n_layers: int):
    """Every leaf the check compares: ``leaf(i, key, *tile)`` reads layer
    ``i``, ``top(key, *tile)`` the tree's top level."""
    s = slice(SAMPLE_ROWS)
    out = {"embed": top("embed"), "out_norm": top("out_norm"),
           "head": top("head", s)}
    for i in range(n_layers):
        for key in MATRICES:
            out[f"L{i}.{key}"] = leaf(i, key, s, s)
        for key in VECTORS:
            out[f"L{i}.{key}"] = leaf(i, key)
    return {k: np.asarray(v, np.float64) for k, v in out.items()}


def _sample(tree, n_layers: int):
    """From a parameter (or gradient) tree whose layers are stacked."""
    def leaf(i, key, *tile):
        return tree["layers"][key][(i, *tile)]

    def top(key, *tile):
        return tree[key][tile] if tile else tree[key]

    return _pick(leaf, top, n_layers)


def _positions(seq: int) -> np.ndarray:
    return np.linspace(seq // LOGIT_ROWS - 1, seq - 1, LOGIT_ROWS).astype(int)


def reference_side(trainer, reference, model: dict, tokens: np.ndarray, rt,
                   **switches) -> dict:
    """What the plain reference says of ``tokens`` on the trainer's present
    parameters: loss, every leaf's sampled gradient, logits rows.
    ``switches`` are ``evabyte_lm._statics``'s."""
    import jax

    local = jax.tree_util.tree_map(
        lambda a: a.addressable_shards[0].data, trainer.params)
    toks = jax.device_put(tokens, rt.devices[0])
    loss, grads, rows = reference.loss_and_grads(
        local, toks, model, positions=_positions(tokens.shape[1]), **switches)
    out = {"loss": float(loss), "grads": _sample(grads, model["n_layers"]),
           "logits": np.asarray(rows[0], np.float64)}
    del grads
    return out


def program_side(trainer, model: dict, tokens: np.ndarray) -> dict:
    """The same from the program: the forward pass's logits rows, then the
    loss and gradients of ``lm_loss`` under the trainer's configuration (what
    its step differentiates), the sampled leaves of them."""
    import jax
    import jax.numpy as jnp

    from multiverso_tpu.models import transformer_forward
    from multiverso_tpu.models.transformer import lm_loss

    cfg, mesh = trainer.cfg, trainer.mesh
    positions = _positions(tokens.shape[1])
    toks = jnp.asarray(tokens)
    logits = jax.jit(lambda p, t: transformer_forward(p, t, cfg, mesh)[
        0, positions].astype(jnp.float32))(trainer.params, toks)
    logits = np.asarray(logits, np.float64)
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p, t: lm_loss(p, t, cfg, mesh)))(trainer.params, toks)
    return {"loss": float(loss), "logits": logits,
            "grads": _sample(grads, model["n_layers"])}


def _rel(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def compare(prog: dict, ref: dict, reference, tokens_shape) -> dict:
    errs = {k: _rel(prog["grads"][k], ref["grads"][k]) for k in ref["grads"]}
    pool = {k: v for k, v in errs.items() if k.split(".")[-1] in POOL}
    rest = {k: v for k, v in errs.items() if k not in pool}
    logit_errs = [_rel(prog["logits"][j], ref["logits"][j])
                  for j in range(ref["logits"].shape[0])]
    out = {"loss_system": prog["loss"], "loss_reference": ref["loss"],
           "loss_abs_err": abs(prog["loss"] - ref["loss"]),
           "logits_rel_err": logit_errs, "worst_logits": max(logit_errs),
           "median_logits": float(np.median(logit_errs)),
           "grad_rel_err": errs, "worst": max(rest.values()),
           "worst_pool": max(pool.values()),
           "loss_atol": reference.LOSS_ATOL,
           "logits_rtol": reference.LOGITS_RTOL,
           "grad_rtol": reference.GRAD_RTOL,
           "grad_rtol_pool": reference.GRAD_RTOL_POOL,
           "shape": list(tokens_shape)}
    out["ok"] = bool(
        out["loss_abs_err"] <= reference.LOSS_ATOL
        and out["median_logits"] <= reference.LOGITS_RTOL
        and out["worst"] <= reference.GRAD_RTOL
        and out["worst_pool"] <= reference.GRAD_RTOL_POOL
        and all(np.isfinite(v) for v in errs.values()))
    return out


# ------------------------------------------ the step applies the gradients
def step_side(trainer, model: dict, tokens: np.ndarray) -> dict:
    """One step of the timed program (``train_step_async``) on ``tokens``:
    its loss and every sampled leaf before and after it."""
    before = _sample(trainer.params, model["n_layers"])
    loss = float(trainer.train_step_async(tokens))
    return {"loss": loss, "before": before,
            "after": _sample(trainer.params, model["n_layers"])}


def step_compare(step: dict, prog: dict, lr: float, reference) -> dict:
    """Each leaf after the step against ``old - lr * gradient`` as the SGD
    updater rounds it (float32 throughout), ``gradient`` being
    ``program_side``'s: ``|new - wanted| / |old - wanted|`` a leaf (the
    module docstring).  ``unresolved`` names the leaves whose wanted step
    rounds to nothing everywhere."""
    f32 = np.float32
    errs, unresolved = {}, []
    for k, old in step["before"].items():
        old = old.astype(f32)
        wanted = (old - f32(lr) * prog["grads"][k].astype(f32)).astype(
            np.float64)
        moved = np.linalg.norm(wanted - old)
        if moved == 0:
            unresolved.append(k)
            continue
        errs[k] = float(np.linalg.norm(step["after"][k] - wanted) / moved)
    out = {"rel_err": errs, "worst": max(errs.values(), default=np.inf),
           "unresolved": unresolved,
           "loss_abs_err": abs(step["loss"] - prog["loss"]),
           "rtol": reference.STEP_RTOL, "loss_atol": reference.LOSS_ATOL}
    out["ok"] = bool(out["worst"] <= reference.STEP_RTOL and not unresolved
                     and out["loss_abs_err"] <= reference.LOSS_ATOL)
    return out


def step_controls(step: dict, prog: dict, lr: float, reference) -> dict:
    """What ``step_compare`` says of an updater at fault, each in float32 as
    an updater would round it: a step that left ``phi`` and ``mu`` as they
    were, one of twice the rate, one the wrong way, and one that applied
    another gradient (here: each leaf's own, reversed end to end)."""
    f32 = np.float32

    def moved(change):
        after = {k: change(k, step["before"][k].astype(f32),
                           prog["grads"][k].astype(f32)).astype(np.float64)
                 for k in step["before"]}
        return step_compare(dict(step, after=after), prog, lr, reference)

    return {
        "pool_unchanged": moved(
            lambda k, old, g: old if k.split(".")[-1] in POOL
            else old - f32(lr) * g),
        "twice_the_rate": moved(lambda k, old, g: old - f32(2 * lr) * g),
        "the_wrong_way": moved(lambda k, old, g: old + f32(lr) * g),
        "another_gradient": moved(
            lambda k, old, g: old - f32(lr) * g.ravel()[::-1].reshape(
                g.shape))}


# ------------------------------------------------------ the attention alone
def _rounded(x) -> np.ndarray:
    """``x`` rounded to bfloat16, the program's operand type, as float32: both
    sides of the attention's check then read the same numbers."""
    import jax.numpy as jnp

    return np.asarray(jnp.asarray(x, jnp.bfloat16).astype(jnp.float32))


def scaled_inputs(model: dict, seq: int, seed: int):
    """Seeded ``(q, k, v, phi, mu, d_o)`` of one sequence of ``seq``
    positions: q, k ``[1, H, seq, D]`` standard normal times 1.5 (scores of a
    few units, so that rounding them shows), v and the cotangent standard
    normal, all rounded to bfloat16; ``phi`` and ``mu`` ``[H, D]`` float32
    standard normal (a pooling far from uniform)."""
    rng = np.random.default_rng(seed)
    H, D = model["n_heads"], model["head_dim"]

    def draw(scale=1.0):
        return _rounded(scale * rng.standard_normal((1, H, seq, D)))

    return (draw(1.5), draw(1.5), draw(), rng.standard_normal(
        (H, D)).astype(np.float32), rng.standard_normal(
            (H, D)).astype(np.float32), draw())


def layer_inputs(trainer, reference, model: dict, tokens: np.ndarray, rt):
    """``(q, k, v, phi, mu, d_o)`` as the timed model's LAST layer hands them
    to its attention on ``tokens``: q, k (rotated) and v ``[1, H, seq, D]``
    from the reference's forward pass on the trainer's present parameters,
    rounded to bfloat16; that layer's own ``phi`` and ``mu``; the cotangent
    seeded standard normal (the step's own is the backward pass's to know)."""
    import jax

    local = jax.tree_util.tree_map(
        lambda a: a.addressable_shards[0].data, trainer.params)
    layer = model["n_layers"] - 1
    q, k, v = reference.attention_inputs(
        local, jax.device_put(tokens, rt.devices[0]), model, layer)
    rng = np.random.default_rng(rt.seed)
    return (_rounded(q), _rounded(k), _rounded(v),
            np.asarray(local["layers"]["phi"][layer], np.float32),
            np.asarray(local["layers"]["mu"][layer], np.float32),
            _rounded(rng.standard_normal(q.shape)))


def attention_program(inputs, model: dict):
    """``ops/flash_eva.py``'s summariser and attention and their backward on
    ``inputs`` (``layer_inputs`` / ``scaled_inputs``): ``(o, (dq, dk, dv,
    dphi, dmu))``."""
    import jax
    import jax.numpy as jnp

    from multiverso_tpu.ops.flash_eva import eva_attention, summarise

    scale = model["head_dim"] ** -0.5
    window, chunk = model["eva_window"], model["eva_chunk"]

    def attend(q, k, v, phi, mu):
        kbar, vbar = summarise(k, v, phi, mu, scale, chunk)
        return eva_attention(q, k, v, kbar, vbar, window, chunk, scale=scale)

    def run(q, k, v, phi, mu, d_o):
        o, pull = jax.vjp(attend, q, k, v, phi, mu)
        return o, pull(d_o)

    q, k, v, phi, mu, d_o = inputs
    bf = jnp.bfloat16
    return jax.jit(run)(jnp.asarray(q, bf), jnp.asarray(k, bf),
                        jnp.asarray(v, bf), jnp.asarray(phi),
                        jnp.asarray(mu), jnp.asarray(d_o, bf))


def attention_compare(got, want, reference, scaled: bool = False) -> dict:
    """``scaled``: under ``scaled_inputs``' limits, else ``layer_inputs``',
    which hold ``dphi`` by a limit of its own (the gradient that tells
    bfloat16 statistics on the model's inputs)."""
    rtol, grad_rtol, phi_rtol = (
        (reference.ATTN_SCALED_RTOL, reference.ATTN_SCALED_GRAD_RTOL,
         reference.ATTN_SCALED_GRAD_RTOL) if scaled else
        (reference.ATTN_RTOL, reference.ATTN_GRAD_RTOL,
         reference.ATTN_PHI_RTOL))
    grads = {f"d{name}": _rel(g, w)
             for name, g, w in zip(ATTN_INPUTS, got[1], want[1])}
    out = {"out_rel_err": _rel(got[0], want[0]), "grad_rel_err": grads,
           "worst_grad": max(grads.values()), "dphi": grads["dphi"],
           "out_rtol": rtol, "grad_rtol": grad_rtol, "phi_rtol": phi_rtol}
    out["ok"] = bool(out["out_rel_err"] <= rtol
                     and out["worst_grad"] <= grad_rtol
                     and out["dphi"] <= phi_rtol)
    return out


def attention_check(reference, model: dict, inputs,
                    scaled: bool = False) -> dict:
    return attention_compare(attention_program(inputs, model),
                             reference.attention_and_grads(*inputs, model),
                             reference, scaled)


def reference_check(trainer, reference, model: dict, tokens: np.ndarray,
                    lr: float, rt) -> dict:
    """Everything the module docstring compares at the check's shape; the
    step comes last, for it moves the parameters the rest reads."""
    ref = reference_side(trainer, reference, model, tokens, rt)
    prog = program_side(trainer, model, tokens)
    out = compare(prog, ref, reference, tokens.shape)
    del ref
    out["attention"] = attention_check(
        reference, model, layer_inputs(trainer, reference, model, tokens, rt))
    out["attention_scaled"] = attention_check(
        reference, model, scaled_inputs(model, tokens.shape[1], rt.seed),
        scaled=True)
    out["step"] = step_compare(step_side(trainer, model, tokens), prog, lr,
                               reference)
    out["ok"] = all([out["ok"], out["attention"]["ok"],
                     out["attention_scaled"]["ok"], out["step"]["ok"]])
    return out


def controls(reference, model: dict, sets: dict, whole=None,
             names=tuple(CONTROLS)) -> dict:
    """What the check says of a program in a lower precision: the reference
    with ``CONTROLS``' switches in the program's place, ``{name: {set: .,
    "model": .}}``.  A set of ``sets`` (``{"attention": layer_inputs,
    "attention_scaled": scaled_inputs}``): through ``attention_compare`` (the
    switches that reach the attention).  ``model``, with ``whole = (trainer,
    tokens, rt)``: through ``compare`` at the trainer's parameters."""
    want = {key: reference.attention_and_grads(*inputs, model)
            for key, inputs in sets.items()}
    if whole:
        trainer, tokens, rt = whole
        sound = reference_side(trainer, reference, model, tokens, rt)
    out = {}
    for name in names:
        switches = CONTROLS[name]
        out[name] = {}
        if not {"residual", "logits"} & set(switches):
            for key, inputs in sets.items():
                out[name][key] = attention_compare(
                    reference.attention_and_grads(*inputs, model, **switches),
                    want[key], reference, scaled=key == "attention_scaled")
        if whole:
            out[name]["model"] = compare(
                reference_side(trainer, reference, model, tokens, rt,
                               **switches),
                sound, reference, tokens.shape)
    return out


def stream_dtypes(text: str, batch: int, seq: int, model: dict) -> dict:
    """A walk of a lowered step's text for the residual stream ``[batch, seq,
    dim]`` and the logits ``[batch, seq, heads x vocab]``:

    - ``f32_adds`` / ``bf16_adds``: the sums of that shape by dtype (the sums
      of a normed input's cotangents are bfloat16 either way);
    - ``carries``: the dtypes in which a ``while`` (the layers' scan, both
      passes) carries that shape;
    - ``rounded``: the float32 sums of that shape BOTH of whose operands were
      just converted from bfloat16, and the float32 values of that shape a
      function or a loop's body hands back just converted from bfloat16: 0
      where the stream goes from sum to sum unrounded (an operand of every
      sum is the stream itself: an argument, an earlier sum, a call's
      result);
    - ``logits``: the dtypes in which the logits' shape appears."""
    import re

    shape = f"tensor<{batch}x{seq}x{model['dim']}x"
    f32, bf16, name = re.escape(shape + "f32>"), re.escape(shape + "bf16>"), \
        r"(%[\w#]+)"
    logits = f"{batch}x{seq}x{model['n_pred_heads'] * model['vocab_size']}"
    widened = re.compile(
        rf"{name} = stablehlo\.convert \S+ : \({bf16}\) -> {f32}")
    add = re.compile(rf"%[\w#]+ = stablehlo\.add {name}, {name} : "
                     rf"{re.escape(shape)}(f32|bf16)>")
    handed = re.compile(r"(?:stablehlo\.|func\.)?return (%.*?) : (.*)")
    adds, carries, rounded = {"f32": 0, "bf16": 0}, set(), 0
    from_bf16 = set()              # the names are a function's own
    for line in map(str.strip, text.splitlines()):
        if line.startswith("func.func"):
            from_bf16 = set()
        elif "stablehlo.while(" in line:
            carries.update(re.findall(rf"{re.escape(shape)}(\w+)>", line))
        elif found := widened.match(line):
            from_bf16.add(found[1])
        elif found := add.match(line):
            adds[found[3]] += 1
            rounded += found[3] == "f32" and {found[1], found[2]} <= from_bf16
        elif found := handed.match(line):
            rounded += sum(
                kind == shape + "f32>" and value in from_bf16
                for value, kind in zip(found[1].split(", "),
                                       found[2].split(", ")))
    return {"f32_adds": adds["f32"], "bf16_adds": adds["bf16"],
            "carries": sorted(carries), "rounded": int(rounded),
            "logits": sorted(set(re.findall(rf"tensor<{logits}x(\w+)>",
                                            text)))}


def stream_is_float32(found: dict) -> bool:
    """``stream_dtypes``' reading of a step whose stream and logits are
    float32 from end to end: a layer's two sums and their replay under remat
    (a bfloat16 stream has no float32 sum of that shape at all)."""
    return (found["f32_adds"] >= 4 and found["rounded"] == 0
            and found["carries"] == ["f32"] and found["logits"] == ["f32"])


class Session:
    def __init__(self, cell, rt):
        import jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from multiverso_tpu import metrics
        from multiverso_tpu.models import TransformerConfig, TransformerTrainer
        from multiverso_tpu.updaters import AddOption

        config, traffic = cell.config, cell.traffic
        _check_published(config)
        self.model = model = dict(config["model"])
        self.batch, self.seq = int(traffic["batch"]), int(traffic["seq"])
        axes, shape = traffic["mesh"]["axes"], traffic["mesh"]["shape"]
        if int(np.prod(shape)) != cell.chips:
            raise ValueError(
                f"{cell.name}: mesh {shape} on {cell.chips} chips")
        self.mesh = Mesh(np.asarray(rt.devices).reshape(shape), tuple(axes))
        self.chips = cell.chips
        lr = float(config["trainer"]["learning_rate"])
        # Counted by the program at trace time; read as the change since
        # this session began.
        self._traced = {
            f"{name}_{path}": metrics.counter(
                f"attention.{name}_traced",
                {"window": str(model["eva_window"]),
                 "chunk": str(model["eva_chunk"]), "path": path})
            for name in ("eva", "eva_bwd")
            for path in ("jnp", "mosaic", "interpret")}
        self._traced_before = {p: c.value for p, c in self._traced.items()}

        t0 = time.perf_counter()
        self.trainer = TransformerTrainer(
            TransformerConfig(**model), self.mesh,
            updater_type=config["trainer"]["updater_type"],
            option=AddOption(learning_rate=lr), seed=rt.seed)
        jax.block_until_ready(self.trainer.params)
        init_s = time.perf_counter() - t0

        generator = load_module(cell.search, "generators",
                                traffic["generator"])
        vocab = model["vocab_size"]
        self.stream = generator.batches(traffic, vocab, rt.seed)
        check_tokens = next(generator.batches(
            dict(traffic, **traffic["check"]), vocab, rt.seed, stream=1))
        self.place_on = NamedSharding(self.mesh, P(axes[0], None))

        t0 = time.perf_counter()
        reference = load_module(cell.search, "reference", config["reference"])
        self.check = reference_check(self.trainer, reference, model,
                                     check_tokens, lr, rt)
        check_s = time.perf_counter() - t0
        rt.log(reference_check=self.check)

        t0 = time.perf_counter()
        first = next(self.stream)
        lowered = self.trainer.lowered_step(first)
        self.stream_dtypes = stream_dtypes(lowered.as_text(), self.batch,
                                           self.seq, model)
        compiled = lowered.compile()
        del lowered
        self.peak_bytes = compiled_peak_bytes(compiled)
        self.hlo_texts = [compiled.as_text()] if rt.trace else []
        del compiled
        compile_s = time.perf_counter() - t0

        t0 = time.perf_counter()
        self.repeated = [self.trainer.loss(first)]
        self.repeated += [float(self.trainer.train_step_async(first))
                          for _ in range(2)]
        self.repeated.append(self.trainer.loss(first))
        warm_s = time.perf_counter() - t0
        rt.log(setup_parts_s={"trainer_init": init_s,
                              "reference_check": check_s,
                              "compile_or_load": compile_s,
                              "warm_up": warm_s},
               step_peak_bytes=self.peak_bytes,
               stream_dtypes=self.stream_dtypes,
               repeated_batch_losses=self.repeated)

    def measure(self, rt) -> Measured:
        import jax

        trainer, stream, span = self.trainer, self.stream, rt.span
        done_at, window_losses = [], []
        pending = trainer.train_step_async(
            jax.device_put(next(stream), self.place_on))
        t_open = rt.open_window()
        with span("bench.window"):
            while True:
                with span("bench.make_batch"):
                    tokens = next(stream)
                with span("bench.place"):
                    placed = jax.device_put(tokens, self.place_on)
                with span("bench.enqueue"):
                    loss = trainer.train_step_async(placed)
                with span("bench.fetch"):
                    window_losses.append(float(pending))
                done_at.append(time.perf_counter())
                pending = loss
                if done_at[-1] - t_open >= rt.seconds:
                    break
            with span("bench.fetch"):
                window_losses.append(float(pending))
            done_at.append(time.perf_counter())
        rt.close_window()

        losses = window_losses
        rt.log(losses_first=losses[:LOSSES_LOGGED], steps=len(losses))
        steps = len(done_at) - 1                # completed after the first
        step_s = step_seconds(done_at)
        model = self.model
        tokens_per_step = self.batch * self.seq
        finite = [bool(np.isfinite(v)) for v in losses]
        traced = {p: c.value - self._traced_before[p]
                  for p, c in self._traced.items()}
        rt.log(steps_in_window=len(done_at), step_s=step_s,
               last_loss=losses[-1], attention_traced=traced)
        return Measured(
            attempted=len(window_losses),
            failed=sum(1 for v in window_losses if not np.isfinite(v)),
            end_to_end={
                "tokens_per_chip_s":
                    tokens_per_step / step_s["median"] / self.chips},
            checks={
                "reference agrees": self.check["ok"],
                "losses finite": all(finite) and bool(
                    np.all(np.isfinite(self.repeated))),
                "loss fell on the repeated batch":
                    bool(self.repeated[2] < self.repeated[1]
                         and self.repeated[3] < self.repeated[0]),
                "the stream and the logits are float32 in the step":
                    stream_is_float32(self.stream_dtypes),
                "no attention on the jnp path":
                    traced["eva_jnp"] == 0 and traced["eva_bwd_jnp"] == 0,
                "both passes traced through the kernels":
                    traced["eva_mosaic"] + traced["eva_interpret"] > 0
                    and traced["eva_bwd_mosaic"]
                    + traced["eva_bwd_interpret"] > 0},
            facts={
                "runner": "lm_train_eva", "chips": self.chips,
                "steps": steps,
                "step_s": step_s["median"], "step_s_mean": step_s["mean"],
                "tokens_per_step": tokens_per_step,
                "flops_per_step": flops_eva.train_flops(
                    model, self.batch, self.seq),
                "eva_flops_per_step": flops_eva.eva_flops(
                    model, self.batch, self.seq),
                "eva_bytes_per_step": flops_eva.eva_bytes(
                    model, self.batch, self.seq)},
            hlo_texts=self.hlo_texts, compiled_peak_bytes=self.peak_bytes,
            compared=compared(self.check, COMPARED))


def setup(cell, rt) -> Session:
    return Session(cell, rt)


def main(argv) -> int:
    """``--seeds a,b,... [--controls 1]``: the check's readings at the cell's
    check shape on this machine's device, a JSON line a seed (a trainer a
    seed): the program against the reference (``program``), the attention
    alone on both sets of inputs, each control of ``CONTROLS`` in the
    program's place on both sets, the step and ``step_controls``; with
    ``--controls 1`` each control through the whole model too."""
    import json
    import types

    import jax
    from jax.sharding import Mesh

    from benchmarks.harness import load_cell
    from multiverso_tpu.models import TransformerConfig, TransformerTrainer
    from multiverso_tpu.updaters import AddOption

    seeds = [int(s) for s in argv[argv.index("--seeds") + 1].split(",")]
    whole = "--controls" in argv and argv[argv.index("--controls") + 1] == "1"
    cell = load_cell("evabyte-6.5b-l4.zipf-bytes-seq16k-b1")
    model, traffic = cell.config["model"], cell.traffic
    lr = float(cell.config["trainer"]["learning_rate"])
    reference = load_module(cell.search, "reference",
                            cell.config["reference"])
    generator = load_module(cell.search, "generators", traffic["generator"])
    devices = jax.devices()[:1]
    for seed in seeds:
        rt = types.SimpleNamespace(devices=devices, seed=seed)
        trainer = TransformerTrainer(
            TransformerConfig(**model), Mesh(np.asarray(devices), ("dp",)),
            updater_type=cell.config["trainer"]["updater_type"],
            option=AddOption(learning_rate=lr), seed=seed)
        tokens = next(generator.batches(
            dict(traffic, **traffic["check"]), model["vocab_size"], seed,
            stream=1))
        sets = {"attention": layer_inputs(trainer, reference, model, tokens,
                                          rt),
                "attention_scaled": scaled_inputs(model, tokens.shape[1],
                                                  seed)}
        line = {"seed": seed}
        for key, inputs in sets.items():
            line[key] = attention_check(reference, model, inputs,
                                        scaled=key == "attention_scaled")
        line["controls"] = controls(reference, model, sets,
                                    (trainer, tokens, rt) if whole else None)
        del sets
        prog = program_side(trainer, model, tokens)
        line["program"] = compare(
            prog, reference_side(trainer, reference, model, tokens, rt),
            reference, tokens.shape)
        step = step_side(trainer, model, tokens)
        line["step"] = step_compare(step, prog, lr, reference)
        line["step_controls"] = step_controls(step, prog, lr, reference)
        del trainer
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
