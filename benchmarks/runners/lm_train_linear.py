"""Runner ``lm_train_linear``: language-model training through
``multiverso_tpu.models.TransformerTrainer`` for a configuration whose layers
carry a recurrent state (Kimi Delta Attention through the chunked scan of
``ops/kda.py``) beside latent attention, with sigmoid bias-corrected routing
limited by groups over a share of the experts.

``lm_train_latent`` checks Xing's published keys and samples Xing's leaves,
so this is its sibling: the same set-up and the same window loop
(``lm_train``'s docstring: trainer, reference check through a step of the
sample's shape, the cell's step compiled with its memory account, two warm-up
steps on one batch, then steps enqueued one ahead on fresh seeded batches, the
rate from the median time between completions; no settling: six seeds spread
by 0.09% without it), the same ``correct`` checks, and its own:

- published keys held equal to the ``model`` group (``_check_published``);
- the reference check (``reference_side`` / ``program_side`` / ``compare``),
  one train step of the check's shape (1 x 2048: 32 chunks of the scan)
  against ``benchmarks/reference/ling_lm.py``: the loss; the logits at
  ``LOGIT_ROWS`` positions spread over the sequence (the forward pass alone,
  the last of them 2047 tokens into the carried state), held by the MEDIAN
  of the rows' distances (the worst row is one row's luck: 0.06-0.29 over
  22 seeds where the median reads 0.052-0.069; it is logged); (old - new) / lr of
  sampled tiles of every kind of leaf of three layers (``SAMPLED``: the
  leading linear layer with the dense FFN, a linear-routed layer inside the
  scanned run, the latent-routed layer) against its gradient, in three
  classes with a bound each: the decay's own leaves (``A_log``, ``dt_bias``,
  ``wf``), the routed experts' path (routers, held experts' ``w2``), the
  rest (the three convolution kernels, ``wq``, ``wk``, ``wv``, ``wb``,
  ``wg``, ``wo``, the head norm's gain, the latent layer's ``wq``, ``wkv_a``,
  ``wkv_b``, the shared expert, the norms' gains, embedding rows); the
  correction bias of every routed layer after the step against the rule's;
  and the tokens that kept the held experts' group, a routed layer
  (``TransformerTrainer.kept``), against the reference's count
  (``kept_mismatch``: what a dropped group mask moves from about a half of
  the tokens to all of them);
- **the scan alone** (``scan_check``): ``ops/kda.py:kda`` and its backward on
  seeded inputs of the check's shape, drawn as a linear layer makes them,
  against the reference's token-by-token recurrence: the output and the five
  gradients, before six layers of bfloat16 matmuls cover what a narrower
  state or decay moves;
- the loss lower after the two warm-up steps on one batch than before them;
- the scan traced through its kernel
  (``attention.linear_traced{heads=,chunk=,path=mosaic|interpret}``, none with
  ``path=jnp``), the latent layer through the two-width kernel, no attention
  on the jnp path, sigmoid routing with its groups traced
  (``moe.traced{...,groups=,kept=}``);
- facts from ``benchmarks/flops_ling.py``, the routed part from the routes
  the steps themselves returned, the kept-group counts from ``kept``.

**What the check refuses** is shown by ``controls``: the reference itself with
one of its switches thrown (``CONTROLS``) stands in the program's place and
goes through ``scan_compare`` / ``compare``.  ``python -m
benchmarks.runners.lm_train_linear --seeds ...`` prints the scan's readings,
the program's and the controls', on the chip; ``tests/test_ling.py`` holds
the controls to ``ok: false`` at a small size.

Why this file holds a ``Session`` of its own and does not subclass
``lm_train_latent``'s: that one's ``__init__`` and ``measure`` call Xing's
``_check_published``, ``reference_check`` and ``flops_xing`` by module-level
name and require the streams' and the prediction module's checks
(``hc.traced``, ``loss_parts``' second loss), so a subclass would replace both
methods whole; folding the four ``lm_train*`` window loops into one is a
``benchmark`` PR's (``PERF.md`` section 7).
"""

from __future__ import annotations

import sys
import time

import numpy as np

from benchmarks import flops_ling
from benchmarks.harness import (Measured, compared, compiled_peak_bytes,
                                load_module)
from benchmarks.runners.lm_train import (LOSSES_LOGGED, SAMPLE_ROWS,
                                         step_seconds)

# Published config keys and the program's field for each.
PUBLISHED = {"hidden_size": "dim", "num_attention_heads": "n_heads",
             "head_dim": "head_dim", "intermediate_size": "dense_hidden",
             "moe_intermediate_size": "hidden",
             "moe_shared_expert_intermediate_size": "shared_expert_hidden",
             "kv_lora_rank": "kv_lora_rank",
             "qk_nope_head_dim": "qk_nope_dim",
             "qk_rope_head_dim": "qk_rope_dim", "v_head_dim": "v_head_dim",
             "num_experts": "experts_held", "num_experts_per_tok": "top_k",
             "n_group": "n_group", "topk_group": "topk_group",
             "norm_topk_prob": "norm_topk_prob",
             "routed_scaling_factor": "routed_scale",
             "score_function": "router_scoring",
             "short_conv_kernel_size": "linear_conv_kernel",
             "kda_lower_bound": "kda_lower_bound",
             "layer_group_size": "layer_period",
             "vocab_size": "vocab_size", "num_hidden_layers": "n_layers",
             "rms_norm_eps": "norm_eps", "rope_theta": "rope_theta",
             "max_position_embeddings": "max_seq"}
SAMPLED = (0, 2, 5)        # linear + dense, linear + routed, latent + routed
LOGIT_ROWS = 16
LINEAR_TILES = ("wq", "wk", "wv", "wf", "wo")      # [rows, columns] tiles
DECAY = ("A_log", "dt_bias", "wf")
SCAN_INPUTS = ("q", "k", "v", "a", "beta")
# The reference's switches (``ling_lm._statics``) that make it a wrong
# program; the last is the router's and does not reach the scan.
CONTROLS = {"state_bf16": {"state_dtype": "bfloat16"},
            "decay_bf16": {"decay_dtype": "bfloat16"},
            "no_decay": {"decay": False},
            "no_group_limit": {"group_limit": False}}
# (reading, its limit) of the reference check, for the result line
COMPARED = (("loss_abs_err", "loss_atol"), ("median_logits", "logits_rtol"),
            ("worst", "grad_rtol"), ("worst_routed", "grad_rtol_routed"),
            ("worst_decay", "grad_rtol_decay"),
            ("bias_mismatch", "bias_mismatch_max"),
            ("kept_mismatch", "kept_mismatch_max"),
            ("scan.out_rel_err", "scan.out_rtol"),
            ("scan.worst_grad", "scan.grad_rtol"))


def _check_published(config: dict) -> None:
    model, name = config["model"], config["name"]

    def same(what, published, run):
        if published != run:
            raise ValueError(f"{name}: {what}={published!r} but the model "
                             f"group runs {run!r}")

    for key, fld in PUBLISHED.items():
        same(key, config[key], model[fld])
    same("published.router_width", config["published"]["router_width"],
         model["num_experts"])
    same("q_lora_rank", config["q_lora_rank"] or 0, model["q_lora_rank"])
    L, dense = config["num_hidden_layers"], config["first_k_dense_replace"]
    group = config["layer_group_size"]
    same("first_k_dense_replace", ["dense"] * dense + ["sparse"] * (L - dense),
         model["mlp_layer_types"])
    same("layer_group_size",
         ["latent_attention" if i % group == group - 1 else "linear_attention"
          for i in range(L)], model["layer_types"])
    same("rope_theta", float(config["rope_theta"]),
         float(model["rope_latent"]["theta"]))
    same("rotary_dim", config["rotary_dim"], model["qk_rope_dim"])
    same("limit lists", [0] * L, config["expert_swiglu_limit_list"])
    same("limit lists", [0] * L, config["share_expert_swiglu_limit_list"])
    for key, run in (("moe_router_enable_expert_bias", True),
                     ("kda_safe_gate", True), ("no_kda_lora", True),
                     ("use_kda_lora", False), ("linear_silu", True),
                     ("use_qk_norm", True), ("group_norm_size", 1),
                     ("use_mla_nope", False), ("value_norm", False),
                     ("up_proj_norm", False), ("use_nGPT", False),
                     ("scale_router_input", False),
                     ("num_kv_heads_for_linear_attn", 0),
                     ("gated_attention_proj_granularity_type", "head_wise"),
                     ("num_key_value_heads", config["num_attention_heads"])):
        same(key, config[key], run)
    same("attn_gate", "per_head", model["attn_gate"])


def _pick(leaf, top, model: dict, rows):
    """The leaves the check compares.  ``leaf(i, key, *tile)`` reads layer
    ``i``; ``top(key, *tile)`` the tree's top level."""
    s = slice(SAMPLE_ROWS)
    out = {"out_norm": top("out_norm"), "embed": top("embed", rows)}
    kinds = list(zip(model["layer_types"], model["mlp_layer_types"]))
    for i in SAMPLED:
        at, (attn, ffn) = f"L{i}", kinds[i]
        for key in ("attn_norm", "mlp_norm"):
            out[f"{at}.{key}"] = leaf(i, key)
        out[f"{at}.wg"] = leaf(i, "wg", s)
        if attn == "linear_attention":
            for key in LINEAR_TILES:
                out[f"{at}.{key}"] = leaf(i, key, s, s)
            for key in ("conv_q", "conv_k", "conv_v"):
                out[f"{at}.{key}"] = leaf(i, key, slice(None), s)
            out.update({f"{at}.A_log": leaf(i, "A_log"),
                        f"{at}.dt_bias": leaf(i, "dt_bias", s),
                        f"{at}.wb": leaf(i, "wb", s),
                        f"{at}.o_norm": leaf(i, "o_norm")})
        else:           # head 0's queries, the latent's tail + rotated key
            out.update({
                f"{at}.wq": leaf(i, "wq", s, s),
                f"{at}.wkv_a": leaf(i, "wkv_a", s, slice(-SAMPLE_ROWS, None)),
                f"{at}.wkv_b": leaf(i, "wkv_b", s, s),
                f"{at}.wo": leaf(i, "wo", s, s),
                f"{at}.kv_a_norm": leaf(i, "kv_a_norm")})
        if ffn == "dense":
            out[f"{at}.w2"] = leaf(i, "w2", s, s)
        else:           # every held expert's tile, the shared expert, router
            out.update({
                f"{at}.w2": leaf(i, "w2", slice(None), s, s),
                f"{at}.shared_w2": leaf(i, "shared_w2", s, s),
                f"{at}.router": leaf(i, "router", s)})
    return {k: np.asarray(v, np.float64) for k, v in out.items()}


def _sample(params, reference, model, rows):
    """From the program's tree, through the reference's ``layer``."""
    def leaf(i, key, *tile):
        value = reference.layer(params["layers"], i)[key]
        return value[tile] if tile else value

    def top(key, *tile):
        return params[key][tile] if tile else params[key]

    return _pick(leaf, top, model, rows)


def leaf_class(name: str) -> str:
    """``decay`` | ``routed`` | ``rest``: the bound a sampled leaf is held
    to.  Layer 0's ``w2`` is a dense one."""
    key = name.split(".")[-1]
    if key in DECAY:
        return "decay"
    if key == "router" or (key == "w2" and not name.startswith("L0.")):
        return "routed"
    return "rest"


def _positions(seq: int) -> np.ndarray:
    return np.linspace(seq // LOGIT_ROWS - 1, seq - 1, LOGIT_ROWS).astype(int)


def reference_side(trainer, reference, model: dict, tokens: np.ndarray, rt,
                   **switches) -> dict:
    """What the plain reference says of ``tokens`` on the trainer's present
    parameters: loss, sampled gradients, logits rows, biases after the rule,
    kept-group counts.  ``switches`` are ``ling_lm._statics``'s."""
    import jax

    rows = np.unique(tokens)[:SAMPLE_ROWS]
    local = jax.tree_util.tree_map(
        lambda a: a.addressable_shards[0].data, trainer.params)
    toks = jax.device_put(tokens, rt.devices[0])
    loss, grads, bias, kept, logits = reference.loss_and_grads(
        local, toks, model, layers=SAMPLED,
        positions=_positions(tokens.shape[1]), **switches)

    def leaf(i, key, *tile):
        value = grads["layers"][i][key]
        return value[tile] if tile else value

    def top(key, *tile):
        return grads[key][tile] if tile else grads[key]

    return {"loss": float(loss), "grads": _pick(leaf, top, model, rows),
            "logits": np.asarray(logits, np.float64),
            "bias": {i: np.asarray(b, np.float64) for i, b in bias.items()},
            "kept": {i: int(k) for i, k in kept.items()}, "rows": rows}


def program_side(trainer, reference, model: dict, tokens: np.ndarray,
                 lr: float, rows) -> dict:
    """The same from the program: the forward pass's logits rows, then one
    train step (loss, (old - new) / lr of the sampled leaves, the biases
    and the counted routes after it)."""
    import jax
    import jax.numpy as jnp

    from multiverso_tpu.models import transformer_forward

    cfg, positions = trainer.cfg, _positions(tokens.shape[1])
    logits = jax.jit(lambda p, t: transformer_forward(p, t, cfg)[
        :, positions].astype(jnp.float32))(trainer.params,
                                           jnp.asarray(tokens))
    logits = np.asarray(logits, np.float64)
    before = _sample(trainer.params, reference, model, rows)
    loss = float(trainer.train_step_async(tokens))
    after = _sample(trainer.params, reference, model, rows)
    routed = [i for i, k in enumerate(model["mlp_layer_types"])
              if k == "sparse"]
    bias = {i: np.asarray(reference.layer(trainer.params["layers"],
                                          i)["router_bias"], np.float64)
            for i in routed}
    return {"loss": loss, "logits": logits,
            "grads": {k: (before[k] - after[k]) / lr for k in before},
            "bias": bias,
            "kept": dict(zip(routed, np.asarray(trainer.kept).tolist()))}


def compare(prog: dict, ref: dict, reference, model: dict,
            tokens_shape) -> dict:
    def rel(got, want):
        return float(np.linalg.norm(got - want) / np.linalg.norm(want))

    errs = {k: rel(prog["grads"][k], ref["grads"][k]) for k in ref["grads"]}
    worst = {c: max(v for k, v in errs.items() if leaf_class(k) == c)
             for c in ("decay", "routed", "rest")}
    logit_errs = [rel(prog["logits"][:, j], ref["logits"][:, j])
                  for j in range(ref["logits"].shape[1])]
    rate = model["router_bias_rate"]
    moved = np.concatenate([np.abs(prog["bias"][i] - ref["bias"][i])
                            > rate / 2 for i in ref["bias"]])
    tokens = int(np.prod(tokens_shape))
    kept = max(abs(prog["kept"][i] - ref["kept"][i]) / tokens
               for i in ref["kept"])
    out = {"loss_system": prog["loss"], "loss_reference": ref["loss"],
           "loss_abs_err": abs(prog["loss"] - ref["loss"]),
           "logits_rel_err": logit_errs, "worst_logits": max(logit_errs),
           "median_logits": float(np.median(logit_errs)),
           "grad_rel_err": errs, "worst": worst["rest"],
           "worst_routed": worst["routed"], "worst_decay": worst["decay"],
           "bias_mismatch": float(moved.mean()), "kept_mismatch": kept,
           "kept_program": prog["kept"], "kept_reference": ref["kept"],
           "loss_atol": reference.LOSS_ATOL,
           "logits_rtol": reference.LOGITS_RTOL,
           "grad_rtol": reference.GRAD_RTOL,
           "grad_rtol_routed": reference.GRAD_RTOL_ROUTED,
           "grad_rtol_decay": reference.GRAD_RTOL_DECAY,
           "bias_mismatch_max": reference.BIAS_MISMATCH,
           "kept_mismatch_max": reference.KEPT_MISMATCH,
           "layers": list(SAMPLED), "shape": list(tokens_shape)}
    out["ok"] = bool(
        out["loss_abs_err"] <= reference.LOSS_ATOL
        and out["median_logits"] <= reference.LOGITS_RTOL
        and worst["rest"] <= reference.GRAD_RTOL
        and worst["routed"] <= reference.GRAD_RTOL_ROUTED
        and worst["decay"] <= reference.GRAD_RTOL_DECAY
        and out["bias_mismatch"] <= reference.BIAS_MISMATCH
        and kept <= reference.KEPT_MISMATCH
        and set(prog["bias"]) == set(ref["bias"])
        and all(np.isfinite(v) for v in errs.values()))
    return out


def _switches(name: str) -> dict:
    import jax.numpy as jnp

    return {k: getattr(jnp, v) if isinstance(v, str) else v
            for k, v in CONTROLS[name].items()}


def scan_inputs(model: dict, seq: int, seed: int):
    """Seeded ``(q, k, v, a, beta, d_o)`` of one sequence of ``seq`` tokens
    as a linear layer hands them to its scan: q and k unit vectors a head (q
    times D^-0.5), the log-decay through the bounded gate with ``A_log`` and
    ``dt_bias`` drawn as the flash-linear-attention library draws them
    (``exp(A_log)`` uniform in (1, 16) a head, ``dt_bias`` the inverse
    softplus of a step log-uniform in (0.001, 0.1) a channel) under a
    standard normal ``h Wf``, beta a sigmoid of one.  q, k, v and the
    cotangent ``d_o`` are rounded to bfloat16, the program's operand type,
    so that both sides read the same numbers."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    H, D = model["n_heads"], model["head_dim"]

    def draw(*shape):
        return rng.standard_normal((1, seq, H) + shape).astype(np.float32)

    def unit(x):
        return x / np.sqrt(np.sum(x * x, axis=-1, keepdims=True) + 1e-6)

    def rounded(x):
        return np.asarray(jnp.asarray(x, jnp.bfloat16).astype(jnp.float32))

    def sigmoid(x):
        return 1.0 / (1.0 + np.exp(-x))

    step = np.exp(rng.uniform(np.log(0.001), np.log(0.1), (H, D)))
    rate = rng.uniform(1.0, 16.0, (H, 1))
    a = model["kda_lower_bound"] * sigmoid(
        rate * (draw(D) + step + np.log(-np.expm1(-step))))
    return (rounded(unit(draw(D)) * D ** -0.5), rounded(unit(draw(D))),
            rounded(draw(D)), a.astype(np.float32),
            sigmoid(draw()).astype(np.float32), rounded(draw(D)))


def scan_program(inputs):
    """``ops/kda.py:kda`` and its backward on ``scan_inputs``: ``(o, (dq, dk,
    dv, da, dbeta))``, the gradients those of ``sum(o * d_o)``."""
    import jax
    import jax.numpy as jnp

    from multiverso_tpu.ops.kda import kda

    def run(q, k, v, a, beta, d_o):
        o, pull = jax.vjp(kda, q, k, v, a, beta)
        return o, pull(d_o)

    q, k, v, a, beta, d_o = inputs
    bf = jnp.bfloat16
    return jax.jit(run)(jnp.asarray(q, bf), jnp.asarray(k, bf),
                        jnp.asarray(v, bf), jnp.asarray(a), jnp.asarray(beta),
                        jnp.asarray(d_o, bf))


def scan_compare(got, want, reference) -> dict:
    """``got`` against ``want``, each ``(o, five gradients)``: relative L2
    distances, the output held to ``SCAN_RTOL`` and the worst gradient to
    ``SCAN_GRAD_RTOL``."""
    def rel(a, b):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        return float(np.linalg.norm(a - b) / np.linalg.norm(b))

    grads = {f"d{name}": rel(g, w)
             for name, g, w in zip(SCAN_INPUTS, got[1], want[1])}
    out = {"out_rel_err": rel(got[0], want[0]), "grad_rel_err": grads,
           "worst_grad": max(grads.values()),
           "out_rtol": reference.SCAN_RTOL,
           "grad_rtol": reference.SCAN_GRAD_RTOL}
    out["ok"] = bool(out["out_rel_err"] <= reference.SCAN_RTOL
                     and out["worst_grad"] <= reference.SCAN_GRAD_RTOL)
    return out


def scan_check(reference, model: dict, seq: int, seed: int) -> dict:
    inputs = scan_inputs(model, seq, seed)
    return scan_compare(scan_program(inputs),
                        reference.scan_and_grads(*inputs), reference)


def reference_check(trainer, reference, model: dict, tokens: np.ndarray,
                    lr: float, rt) -> dict:
    ref = reference_side(trainer, reference, model, tokens, rt)
    prog = program_side(trainer, reference, model, tokens, lr, ref["rows"])
    out = compare(prog, ref, reference, model, tokens.shape)
    out["scan"] = scan_check(reference, model, tokens.shape[1], rt.seed)
    out["ok"] = out["ok"] and out["scan"]["ok"]
    return out


def controls(reference, model: dict, seq: int, seed: int, whole=None,
             names=tuple(CONTROLS)) -> dict:
    """What the check says of a wrong program: the reference with one switch
    thrown (``CONTROLS``) in the program's place, ``{name: {"scan": .,
    "model": .}}``.  ``scan``: through ``scan_compare`` on ``scan_inputs``
    (not for the router's switch).  ``model``, with ``whole = (trainer,
    tokens, rt)``: through ``compare`` at the trainer's parameters."""
    inputs = scan_inputs(model, seq, seed)
    want = reference.scan_and_grads(*inputs)
    if whole:
        trainer, tokens, rt = whole
        sound = reference_side(trainer, reference, model, tokens, rt)
    out = {}
    for name in names:
        switches = _switches(name)
        out[name] = {}
        if "group_limit" not in switches:
            out[name]["scan"] = scan_compare(
                reference.scan_and_grads(*inputs, **switches), want,
                reference)
        if whole:
            out[name]["model"] = compare(
                reference_side(trainer, reference, model, tokens, rt,
                               **switches),
                sound, reference, model, tokens.shape)
    return out


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
        paths = ("jnp", "mosaic", "interpret")
        self._traced = {p: metrics.counter("attention.traced", {"path": p})
                        for p in paths}
        self._traced["latent"] = metrics.counter(
            "attention.latent_traced",
            {"qk": str(model["qk_nope_dim"] + model["qk_rope_dim"]),
             "v": str(model["v_head_dim"])})
        self._traced.update({f"linear_{p}": metrics.counter(
            "attention.linear_traced",
            {"heads": str(model["n_heads"]), "chunk": str(flops_ling.CHUNK),
             "path": p}) for p in paths})
        self._traced["groups"] = metrics.counter(
            "moe.traced", {"dispatch": model["moe_dispatch"],
                           "scoring": model["router_scoring"],
                           "groups": str(model["n_group"]),
                           "kept": str(model["topk_group"])})
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
        compiled = self.trainer.lowered_step(first).compile()
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
               repeated_batch_losses=self.repeated)

    def measure(self, rt) -> Measured:
        import jax

        trainer, stream, span = self.trainer, self.stream, rt.span
        done_at, window_losses, counted = [], [], []
        pending = trainer.train_step_async(
            jax.device_put(next(stream), self.place_on))
        counted.append((trainer.routes, trainer.kept))
        t_open = rt.open_window()
        with span("bench.window"):
            while True:
                with span("bench.make_batch"):
                    tokens = next(stream)
                with span("bench.place"):
                    placed = jax.device_put(tokens, self.place_on)
                with span("bench.enqueue"):
                    loss = trainer.train_step_async(placed)
                counted.append((trainer.routes, trainer.kept))  # on the device
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
        bias_absmax = trainer.router_bias_absmax()

        # Fetched after the window: [steps, routed layers, held experts + 1]
        # (the held experts' routes, elsewhere) and [steps, routed layers]
        # (tokens that kept the held experts' group).
        routes = np.stack([np.asarray(r) for r, _ in counted]).astype(np.int64)
        kept = np.stack([np.asarray(k) for _, k in counted]).astype(np.int64)
        held = routes[:, :, :-1]
        layers = routes.shape[1]
        routes_per_step = layers * tokens_per_step * model["top_k"]
        held_per_step = float(held.sum(axis=(1, 2)).mean())
        per_expert = held.mean(axis=0)               # [layers, held]
        rt.log(steps_in_window=len(done_at), step_s=step_s,
               last_loss=losses[-1], attention_traced=traced,
               router_bias_absmax=bias_absmax,
               held_routes_in_window=held.sum(axis=(1, 2)).tolist(),
               kept_group_tokens={"per_layer": kept.mean(axis=0).tolist(),
                                  "of": tokens_per_step},
               held_routes={"per_step": held_per_step,
                            "of": routes_per_step,
                            "per_layer":
                                held.sum(axis=2).mean(axis=0).tolist(),
                            "expert_max_over_mean":
                                (per_expert.max(axis=1)
                                 / np.maximum(per_expert.mean(axis=1), 1e-9)
                                 ).tolist()})
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
                "no attention on the jnp path":
                    traced["jnp"] == 0 and traced["linear_jnp"] == 0,
                "the scan and the two-width kernel traced":
                    traced["linear_mosaic"] + traced["linear_interpret"] > 0
                    and traced["latent"] > 0
                    and traced["mosaic"] + traced["interpret"] > 0,
                "sigmoid routing limited by groups traced":
                    traced["groups"] > 0,
                "the bias rule moved the bias": 0 < bias_absmax <= (
                    model["router_bias_rate"] * (3.5 + len(losses))),
                "every step's routes add up": bool(np.all(
                    routes.sum(axis=2) == tokens_per_step * model["top_k"])),
                "no token kept more than its share of groups": bool(np.all(
                    (kept >= 0) & (kept <= tokens_per_step)))},
            facts={
                "runner": "lm_train_linear", "chips": self.chips,
                "steps": steps,
                "step_s": step_s["median"], "step_s_mean": step_s["mean"],
                "tokens_per_step": tokens_per_step,
                "held_routes_per_step": held_per_step,
                "routes_per_step": routes_per_step,
                "group_kept_per_step": float(kept.sum(axis=1).mean()),
                "group_tokens_per_step": layers * tokens_per_step,
                "router_bias_absmax": bias_absmax,
                "flops_per_step": flops_ling.train_flops(
                    model, self.batch, self.seq, held_per_step),
                "mla_kernel_flops_per_step": flops_ling.mla_kernel_flops(
                    model, self.batch, self.seq),
                "mla_kernel_bytes_per_step": flops_ling.mla_kernel_bytes(
                    model, self.batch, self.seq),
                "kda_flops_per_step": flops_ling.kda_flops(
                    model, self.batch, self.seq),
                "kda_bytes_per_step": flops_ling.kda_bytes(
                    model, self.batch, self.seq),
                "gmm_held_flops_per_step":
                    flops_ling.routed_flops(model, held_per_step),
                "gmm_held_bytes_per_step":
                    flops_ling.grouped_matmul_bytes(model, held_per_step)},
            hlo_texts=self.hlo_texts, compiled_peak_bytes=self.peak_bytes,
            compared=compared(self.check, COMPARED))


def setup(cell, rt) -> Session:
    return Session(cell, rt)


def main(argv) -> int:
    """``--seeds a,b,...``: the scan's readings at the cell's check shape on
    this machine's device, a JSON line a seed: the program against the
    reference, then each control in the program's place."""
    import json

    from benchmarks.harness import load_cell

    seeds = [int(s) for s in argv[argv.index("--seeds") + 1].split(",")]
    cell = load_cell("ling-3.0-flash-vl-l6.zipf-seq16k-b1")
    model = cell.config["model"]
    reference = load_module(cell.search, "reference",
                            cell.config["reference"])
    seq = int(cell.traffic["check"]["seq"])
    for seed in seeds:
        print(json.dumps({
            "seed": seed, "program": scan_check(reference, model, seq, seed),
            "controls": controls(reference, model, seq, seed)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
