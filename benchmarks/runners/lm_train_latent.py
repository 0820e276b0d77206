"""Runner ``lm_train_latent``: language-model training through
``multiverso_tpu.models.TransformerTrainer`` for a configuration with latent
attention (two score widths, a shared rotated key head), hyper-connection
streams, sigmoid bias-corrected routing over a share of the experts, and a
multi-token-prediction module.

``lm_train_kinds`` checks Laguna's published keys and samples Laguna's
leaves, so this is its sibling: the same set-up and the same window loop
(``lm_train``'s docstring: trainer, reference check through a step of the
sample's shape, the cell's step compiled with its memory account, two
warm-up steps on one batch, then steps enqueued one ahead on fresh seeded
batches, the rate from the median time between completions), the same
``correct`` checks, and its own:

- published keys held equal to the ``model`` group (``_check_published``);
- sampled leaves of three blocks (``SAMPLED``: the leading dense layer, a
  routed layer in the middle of the scan, the prediction module's): tiles of
  ``wq_b`` (head 0, both score parts), ``wkv_a`` (the last 256 columns: 192
  latent ones and the rotated key head's 64), ``wkv_b``, ``wo``, the
  attention's ``phi``, both sub-layers' ``alpha`` and ``b`` (``b`` without
  its four diagonal ``res`` entries, which rest at 8 where a float32 ulp is
  larger than a step's update), the layer's ``w2`` (a dense one, or every
  held expert's), the shared expert's ``w2``, the router, the four norm
  gains; embedding rows, the final norm gain, the module's projection (a
  tile of each half) and its three norm gains;
- the correction bias of every routed block after the check step against
  the reference's (``bias_mismatch``: the share of experts whose bias is
  another);
- both losses (next token, token after next) lower after the two warm-up
  steps on one batch than before them (``TransformerTrainer.loss_parts``);
- attention traced through the ``flash_mla_*`` kernels
  (``attention.latent_traced{qk=,v=}``);
- facts from ``benchmarks/flops_xing.py``, the routed part from the routes
  the steps themselves returned, and the largest ``|router_bias|`` at the
  window's end;
- **settling** (``trainer.settle_steps`` and ``trainer.balance_steps`` of
  the configuration's file), counted as set-up: after the two warm-up steps,
  that many train steps on fresh batches, then that many applications of the
  bias rule alone on one more batch, the weights still
  (``TransformerTrainer.balance_router_bias``).  An untrained router sends
  the tokens unevenly (every token's hidden state shares a large common
  part, and a Zipf batch repeats a few ids), so how many routes reach the 8
  experts held here is the seed's luck: 21,209 to 39,064 of 229,376 a step
  over seven seeds, 0.65-0.69 ms a step per 1,000, and a rate that spread by
  0.53-0.72% where half the bound is 0.5% (PR 32, ``PERF.md`` section 6).
  The bias rule is the model's own cure, but at 0.001 a step it is slower
  than the first steps' learning, which drives routes away from the held
  experts (what the absent ones would add is left out, and at first nothing
  is better than a random expert): 64 settling steps alone left three seeds
  at 15,000, 22,500 and 25,500 held routes a step and 0.76% apart; with 96
  passes of the rule alone after them the same seeds opened their windows at
  23,500-31,300 and lay 0.52% apart, 1.44% without either; with 256 passes
  six seeds read 9,557.2-9,581.9 tokens/s/chip, a spread of 0.104% where the
  same six had read 0.72%.
"""

from __future__ import annotations

import math
import time

import numpy as np

from benchmarks import flops_xing
from benchmarks.harness import (Measured, compared, compiled_peak_bytes,
                                load_module)
from benchmarks.runners.lm_train import (LOSSES_LOGGED, SAMPLE_ROWS,
                                         step_seconds)

# Published config keys and the program's field for each.
PUBLISHED = {"hidden_size": "dim", "num_attention_heads": "n_heads",
             "intermediate_size": "dense_hidden",
             "moe_intermediate_size": "hidden",
             "q_lora_rank": "q_lora_rank", "kv_lora_rank": "kv_lora_rank",
             "qk_nope_head_dim": "qk_nope_dim",
             "qk_rope_head_dim": "qk_rope_dim", "v_head_dim": "v_head_dim",
             "n_routed_experts": "experts_held",
             "num_experts_per_tok": "top_k",
             "norm_topk_prob": "norm_topk_prob",
             "routed_scaling_factor": "routed_scale",
             "scoring_func": "router_scoring",
             "num_nextn_predict_layers": "mtp_layers",
             "hc_mult": "hc_mult", "hc_sinkhorn_iters": "hc_sinkhorn_iters",
             "hc_eps": "hc_eps", "mhc_h_res_clamp_min": "hc_res_clamp_min",
             "mhc_h_res_clamp_max": "hc_res_clamp_max",
             "vocab_size": "vocab_size", "num_hidden_layers": "n_layers",
             "rms_norm_eps": "norm_eps", "rope_theta": "rope_theta",
             "max_position_embeddings": "max_seq"}
# rope_scaling keys and ``Rope``'s field for each.
ROPE = {"factor": "yarn_factor",
        "original_max_position_embeddings": "original_max_seq",
        "beta_fast": "beta_fast", "beta_slow": "beta_slow"}
# (reading, its limit) of the reference check, for the result line
COMPARED = (("loss_abs_err", "loss_atol"), ("worst", "grad_rtol"),
            ("worst_routed", "grad_rtol_routed"),
            ("bias_mismatch", "bias_mismatch_max"))


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
    same("n_shared_experts x moe_intermediate_size",
         config["n_shared_experts"] * config["moe_intermediate_size"],
         model["shared_expert_hidden"])
    L, dense = config["num_hidden_layers"], config["first_k_dense_replace"]
    same("first_k_dense_replace", ["dense"] * dense + ["sparse"] * (L - dense),
         model["mlp_layer_types"])
    same("layer_types", ["latent_attention"] * L, model["layer_types"])
    scaling, rope = config["rope_scaling"], model["rope_latent"]
    same("rope_scaling.type", scaling["type"], "yarn")
    same("rope_theta", float(config["rope_theta"]), float(rope["theta"]))
    for key, fld in ROPE.items():
        same(f"rope_scaling.{key}", float(scaling[key]), float(rope[fld]))

    def mscale(m):          # DeepSeek-V3's yarn_get_mscale
        return 0.1 * m * math.log(scaling["factor"]) + 1.0

    same("rope_scaling.mscale / mscale_all_dim",
         round(mscale(scaling["mscale"]) / mscale(scaling["mscale_all_dim"]),
               12), round(float(rope["attention_factor"]), 12))
    same("softmax mscale", round(mscale(scaling["mscale_all_dim"]), 12),
         round(float(model["attn_mscale"]), 12))
    for key, run in (("tie_word_embeddings", False),
                     ("attention_bias", False), ("n_group", 1),
                     ("topk_group", 1), ("topk_method", "noaux_tc"),
                     ("moe_layer_freq", 1), ("hidden_act", "silu"),
                     ("num_key_value_heads", config["num_attention_heads"])):
        same(key, config[key], run)


def sampled(model: dict):
    """The three blocks sampled: ``(0, a routed layer, "mtp")``."""
    return (0, 1 + (model["n_layers"] - 1) // 2, "mtp")


def _pick(layer_leaf, top_leaf, model: dict, rows):
    """The leaves the check compares.  ``layer_leaf(i, path, *tile)`` reads
    block ``i`` (a layer index or ``"mtp"``), ``path`` a key or a tuple of
    keys; ``top_leaf(path, *tile)`` the tree's top level."""
    s = slice(SAMPLE_ROWS)
    n, dim = model["hc_mult"], model["dim"]
    # b without the res part's diagonal (module docstring)
    keep = np.asarray([j for j in range(2 * n + n * n)
                       if j < 2 * n or (j - 2 * n) % (n + 1)])
    out = {"out_norm": top_leaf("out_norm"), "embed": top_leaf("embed", rows),
           "mtp.proj_h": top_leaf(("mtp", "proj"), s, s),
           "mtp.proj_e": top_leaf(("mtp", "proj"), slice(dim, dim
                                                         + SAMPLE_ROWS), s)}
    for key in ("h_norm", "e_norm", "out_norm"):
        out[f"mtp.{key}"] = top_leaf(("mtp", key))
    for i in sampled(model):
        at = "M" if i == "mtp" else f"L{i}"
        for key in ("attn_norm", "mlp_norm", "q_a_norm", "kv_a_norm"):
            out[f"{at}.{key}"] = layer_leaf(i, key)
        out.update({
            f"{at}.wq_b": layer_leaf(i, "wq_b", s, s),
            f"{at}.wkv_a": layer_leaf(i, "wkv_a", s, slice(-SAMPLE_ROWS,
                                                           None)),
            f"{at}.wkv_b": layer_leaf(i, "wkv_b", s, s),
            f"{at}.wo": layer_leaf(i, "wo", s, s),
            f"{at}.hc_attn.phi": layer_leaf(i, ("hc_attn", "phi"), s)})
        for sub in ("hc_attn", "hc_mlp"):
            out[f"{at}.{sub}.alpha"] = layer_leaf(i, (sub, "alpha"))
            out[f"{at}.{sub}.b"] = layer_leaf(i, (sub, "b"), keep)
        if i == 0:                                  # the dense layer
            out["L0.w2"] = layer_leaf(0, "w2", s, s)
        else:          # every held expert's tile, the shared expert, router
            out.update({
                f"{at}.w2": layer_leaf(i, "w2", slice(None), s, s),
                f"{at}.shared_w2": layer_leaf(i, "shared_w2", s, s),
                f"{at}.router": layer_leaf(i, "router", s)})
    return {k: np.asarray(v, np.float64) for k, v in out.items()}


def _at(tree, path):
    for key in ((path,) if isinstance(path, str) else path):
        tree = tree[key]
    return tree


def _sample(params, model, rows):
    """From the program's tree: layers grouped ``{"lead", "period",
    "trail"}``, a slot's leaves stacked over its repetitions."""
    layers = params["layers"]
    lead, period = layers["lead"], layers["period"]

    def layer_leaf(i, path, *tile):
        if i == "mtp":
            leaf = _at(params["mtp"]["layer"], path)
        elif i < len(lead):
            leaf = _at(lead[i], path)
        else:
            j = i - len(lead)
            leaf = _at(period[j % len(period)], path)
            tile = (j // len(period), *tile)
        return leaf[tile] if tile else leaf

    def top_leaf(path, *tile):
        leaf = _at(params, path)
        return leaf[tile] if tile else leaf

    return _pick(layer_leaf, top_leaf, model, rows)


def _sample_grads(grads, model, rows):
    """From the reference's gradients: ``grads["layers"][i]`` and
    ``grads["mtp"]["layer"]`` are plain layer dicts."""
    def layer_leaf(i, path, *tile):
        tree = (grads["mtp"]["layer"] if i == "mtp" else grads["layers"][i])
        leaf = _at(tree, path)
        return leaf[tile] if tile else leaf

    def top_leaf(path, *tile):
        leaf = _at(grads, path)
        return leaf[tile] if tile else leaf

    return _pick(layer_leaf, top_leaf, model, rows)


def routed(leaf: str) -> bool:
    """Whether a sampled leaf lies on the routed experts' path: a router, or
    the held experts' ``w2`` (layer 0's ``w2`` is a dense one)."""
    return leaf.endswith(".router") or (leaf.endswith(".w2")
                                        and not leaf.startswith("L0."))


def gate_scalar(leaf: str) -> bool:
    """Whether a sampled leaf is a hyper-connection's ``alpha`` or ``b``.  At
    the initial values the gates barely move with the streams (``alpha``
    0.01 times products of size 0.02), so these gradients are sums of a few
    thousand signed terms that nearly cancel, and bfloat16 streams move them
    by their own size: over seven seeds they read 0.003 to 7.9 against the
    float32 reference (``xing_lm``'s docstring).  They are logged, held to
    be finite, and left out of ``GRAD_RTOL``; ``phi``, the same path's
    matrix, is held to it."""
    return leaf.endswith((".alpha", ".b"))


def _biases(params, reference, model) -> dict:
    """``{layer index or "mtp": router_bias [E]}`` of the program's tree."""
    out = {i: reference.layer(params["layers"], i)["router_bias"]
           for i, kind in enumerate(model["mlp_layer_types"])
           if kind == "sparse"}
    out["mtp"] = params["mtp"]["layer"]["router_bias"]
    return {k: np.asarray(v, np.float64) for k, v in out.items()}


def reference_check(trainer, reference, model: dict, tokens: np.ndarray,
                    lr: float, rt) -> dict:
    """One train step on ``tokens`` against the plain reference: the loss,
    (old - new) / lr of the sampled leaves against its gradient, and every
    routed block's correction bias after the step against its rule's."""
    import jax

    rows = np.unique(tokens)[:SAMPLE_ROWS]
    local = jax.tree_util.tree_map(
        lambda a: a.addressable_shards[0].data, trainer.params)
    toks = jax.device_put(tokens, rt.devices[0])
    ref_loss, ref_grads, ref_bias = reference.loss_and_grads(
        local, toks, model, layers=sampled(model)[:2])
    ref_loss = float(ref_loss)
    want = _sample_grads(ref_grads, model, rows)
    ref_bias = {k: np.asarray(v, np.float64) for k, v in ref_bias.items()}
    del ref_grads, local
    before = _sample(trainer.params, model, rows)
    sys_loss = float(trainer.train_step_async(tokens))
    after = _sample(trainer.params, model, rows)
    errs = {k: float(np.linalg.norm((before[k] - after[k]) / lr - want[k])
                     / np.linalg.norm(want[k])) for k in want}
    worst = {kind: max(v for k, v in errs.items()
                       if routed(k) == kind and not gate_scalar(k))
             for kind in (False, True)}
    got_bias = _biases(trainer.params, reference, model)
    rate = model["router_bias_rate"]
    moved = np.concatenate([np.abs(got_bias[k] - ref_bias[k]) > rate / 2
                            for k in ref_bias])
    out = {"loss_system": sys_loss, "loss_reference": ref_loss,
           "loss_abs_err": abs(sys_loss - ref_loss), "grad_rel_err": errs,
           "worst": worst[False], "worst_routed": worst[True],
           "worst_gate_scalar": max(v for k, v in errs.items()
                                    if gate_scalar(k)),
           "bias_mismatch": float(moved.mean()),
           "bias_blocks": len(ref_bias), "loss_atol": reference.LOSS_ATOL,
           "grad_rtol": reference.GRAD_RTOL,
           "grad_rtol_routed": reference.GRAD_RTOL_ROUTED,
           "bias_mismatch_max": reference.BIAS_MISMATCH,
           "blocks": [str(i) for i in sampled(model)],
           "shape": list(tokens.shape)}
    out["ok"] = bool(out["loss_abs_err"] <= reference.LOSS_ATOL
                     and worst[False] <= reference.GRAD_RTOL
                     and worst[True] <= reference.GRAD_RTOL_ROUTED
                     and out["bias_mismatch"] <= reference.BIAS_MISMATCH
                     and set(got_bias) == set(ref_bias)
                     and all(np.isfinite(v) for v in errs.values()))
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
        self.settle_steps = int(config["trainer"]["settle_steps"])
        self.balance_steps = int(config["trainer"]["balance_steps"])
        # Traces of the attention body by path and of the two-width kernel,
        # counted by the program at trace time; read as the change since
        # this session began.
        self._traced = {p: metrics.counter("attention.traced", {"path": p})
                        for p in ("jnp", "mosaic", "interpret")}
        self._traced["latent"] = metrics.counter(
            "attention.latent_traced",
            {"qk": str(model["qk_nope_dim"] + model["qk_rope_dim"]),
             "v": str(model["v_head_dim"])})
        self._traced["sigmoid"] = metrics.counter(
            "moe.traced", {"dispatch": model["moe_dispatch"],
                           "scoring": model["router_scoring"]})
        self._traced["streams"] = metrics.counter(
            "hc.traced", {"n": str(model["hc_mult"])})
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
        parts_before = self.trainer.loss_parts(first)
        self.repeated = [float(self.trainer.train_step_async(first))
                         for _ in range(2)]
        self.parts = [parts_before, self.trainer.loss_parts(first)]
        warm_s = time.perf_counter() - t0

        # Settling: train on fresh batches until the bias rule has spread
        # the routes the untrained router sent to a few experts (module
        # docstring).  The counted routes stay on the device.
        t0 = time.perf_counter()
        self.settling = []
        for _ in range(self.settle_steps):
            loss = self.trainer.train_step_async(
                jax.device_put(next(self.stream), self.place_on))
            self.settling.append((loss, self.trainer.routes))
        # ... and then the rule alone on one more batch, the weights still,
        # until the loads it left uneven are even.
        self.trainer.balance_router_bias(next(self.stream), self.balance_steps)
        jax.block_until_ready(self.trainer.params)
        settle_s = time.perf_counter() - t0
        rt.log(setup_parts_s={"trainer_init": init_s,
                              "reference_check": check_s,
                              "compile_or_load": compile_s,
                              "warm_up": warm_s, "settling": settle_s},
               step_peak_bytes=self.peak_bytes,
               repeated_batch_losses=self.repeated,
               repeated_batch_loss_parts=self.parts)

    def measure(self, rt) -> Measured:
        import jax

        trainer, stream, span = self.trainer, self.stream, rt.span
        done_at, window_losses, routes = [], [], []
        pending = trainer.train_step_async(
            jax.device_put(next(stream), self.place_on))
        routes.append(trainer.routes)
        t_open = rt.open_window()
        with span("bench.window"):
            while True:
                with span("bench.make_batch"):
                    tokens = next(stream)
                with span("bench.place"):
                    placed = jax.device_put(tokens, self.place_on)
                with span("bench.enqueue"):
                    loss = trainer.train_step_async(placed)
                routes.append(trainer.routes)       # stays on the device
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

        # [steps, routed blocks, held experts + 1], fetched after the window
        counted = np.stack([np.asarray(r) for r in routes]).astype(np.int64)
        held = counted[:, :, :-1]
        routes_per_step = counted.shape[1] * tokens_per_step * model["top_k"]
        held_per_step = float(held.sum(axis=(1, 2)).mean())
        per_expert = held.mean(axis=0)               # [blocks, held]
        settled = [int(np.asarray(r)[:, :-1].sum()) for _, r in self.settling]
        rt.log(steps_in_window=len(done_at), step_s=step_s,
               last_loss=losses[-1], attention_traced=traced,
               router_bias_absmax=bias_absmax,
               settling={"steps": self.settle_steps,
                         "balance_steps": self.balance_steps,
                         "losses_every_8th":
                             [float(l) for l, _ in self.settling[::8]],
                         "held_routes_every_4th": settled[::4]},
               held_routes_in_window=held.sum(axis=(1, 2)).tolist(),
               held_routes={"per_step": held_per_step,
                            "of": routes_per_step,
                            "per_layer":
                                held.sum(axis=2).mean(axis=0).tolist(),
                            "expert_max_over_mean":
                                (per_expert.max(axis=1)
                                 / per_expert.mean(axis=1)).tolist()})
        (ce0, mtp0), (ce1, mtp1) = self.parts
        kernel_flops = flops_xing.mla_kernel_flops(model, self.batch,
                                                   self.seq)
        kernel_bytes = flops_xing.mla_kernel_bytes(model, self.batch,
                                                   self.seq)
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
                    bool(self.repeated[1] < self.repeated[0]),
                "both losses fell on the repeated batch":
                    bool(ce1 < ce0 and mtp1 < mtp0),
                "no attention on the jnp path": traced["jnp"] == 0,
                "attention traced through the two-width kernel":
                    traced["latent"] > 0
                    and traced["mosaic"] + traced["interpret"] > 0,
                "sigmoid routing and the streams traced":
                    traced["sigmoid"] > 0 and traced["streams"] > 0,
                "the bias rule moved the bias": 0 < bias_absmax <= (
                    model["router_bias_rate"]
                    * (3.5 + self.settle_steps + self.balance_steps
                       + len(losses))),
                "every step's routes add up": bool(np.all(
                    counted.sum(axis=2) == tokens_per_step * model["top_k"]))},
            facts={
                "runner": "lm_train_latent", "chips": self.chips,
                "steps": steps,
                "step_s": step_s["median"], "step_s_mean": step_s["mean"],
                "tokens_per_step": tokens_per_step,
                "held_routes_per_step": held_per_step,
                "routes_per_step": routes_per_step,
                "router_bias_absmax": bias_absmax,
                "flops_per_step": flops_xing.train_flops(
                    model, self.batch, self.seq, held_per_step),
                "mla_kernel_flops_per_step": kernel_flops,
                "mla_kernel_bytes_per_step": kernel_bytes,
                "gmm_held_flops_per_step":
                    flops_xing.routed_flops(model, held_per_step),
                "gmm_held_bytes_per_step":
                    flops_xing.grouped_matmul_bytes(model, held_per_step)},
            hlo_texts=self.hlo_texts, compiled_peak_bytes=self.peak_bytes,
            compared=compared(self.check, COMPARED))


def setup(cell, rt) -> Session:
    return Session(cell, rt)
