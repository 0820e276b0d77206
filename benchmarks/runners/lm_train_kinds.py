"""Runner ``lm_train_kinds``: language-model training through
``multiverso_tpu.models.TransformerTrainer`` for a configuration whose layers
differ in kind (window and full attention with their own head counts,
grouped K/V heads, a per-head gate, a leading dense layer, routed layers that
hold a share of the experts beside a shared expert).

``lm_train`` cannot run such a configuration (its published-key check wants
multi-head attention with ``dim == heads * head_dim``, its sample reads one
stacked tree, its facts count every layer alike), so this is its sibling:
the same set-up and the same window loop (``lm_train``'s docstring: trainer,
reference check through a step of the sample's shape, the cell's step
compiled with its memory account, two warm-up steps on one batch, then steps
enqueued one ahead on fresh seeded batches, the rate from the median time
between completions, ``step_seconds``), the same ``correct`` checks, and its
own:

- published keys held equal to the ``model`` group (``_check_published``);
- sampled leaves of three layers, one of each kind (``SAMPLED_LAYERS``: the
  leading dense full-attention layer, a sliding routed layer, the full
  routed layer): tiles of ``wq``, ``wk``, the gate, the layer's ``w2`` (a
  dense one, or the held experts'), the shared expert's ``w2``, the router,
  the norm gains; embedding rows and the final norm gain;
- facts from ``benchmarks/flops_laguna.py``, the routed part counted from the
  routes the steps themselves returned (``TransformerTrainer.routes``: kept
  on the device during the window and fetched after it);
- two bounds on the sampled leaves' gradients, the reference's
  ``GRAD_RTOL`` for the leaves outside the routed experts and
  ``GRAD_RTOL_ROUTED`` for the routers and the held experts' ``w2``
  (``routed``), whose gradients move with every route the system's
  bfloat16 hidden state sends elsewhere than the reference's float32 one
  (``laguna_lm``'s docstring has the readings);
- **settling** (``trainer.settle_steps`` of the configuration's file, a fixed
  number, the same for every seed; counted as set-up): after the two warm-up
  steps, that many un-timed train steps of the cell's own compiled step on
  fresh batches of the cell's stream.  How many routes reach the 16 experts
  held here is the seed's luck at first (3.6-5.5% of them where even routing
  gives 6.25%) and drifts as the router learns away from experts whose
  absent peers add nothing; the step's time follows it (0.28 ms per 1,000
  held routes), so unsettled the rate spread by 0.32-0.55% between seeds
  where half the bound is 0.5% (``PERF.md`` section 6, PR 39, has the curve
  the number was chosen from).
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks import flops_laguna
from benchmarks.harness import (Measured, compared, compiled_peak_bytes,
                                load_module)
from benchmarks.runners.lm_train import (LOSSES_LOGGED, SAMPLE_ROWS,
                                         step_seconds)

# Published config keys and the program's field for each.
PUBLISHED = {"hidden_size": "dim", "num_attention_heads": "n_heads",
             "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
             "intermediate_size": "dense_hidden",
             "moe_intermediate_size": "hidden",
             "shared_expert_intermediate_size": "shared_expert_hidden",
             "num_experts": "experts_held", "num_experts_per_tok": "top_k",
             "norm_topk_prob": "norm_topk_prob",
             "moe_routed_scaling_factor": "routed_scale",
             "sliding_window": "sliding_window",
             "vocab_size": "vocab_size", "num_hidden_layers": "n_layers",
             "rms_norm_eps": "norm_eps",
             "max_position_embeddings": "max_seq",
             "layer_types": "layer_types",
             "mlp_layer_types": "mlp_layer_types",
             "num_attention_heads_per_layer": "heads_per_layer"}
# rope_parameters.<kind> keys and ``Rope``'s field for each.
ROPE = {"rope_theta": "theta", "partial_rotary_factor": "rotary_factor",
        "factor": "yarn_factor",
        "original_max_position_embeddings": "original_max_seq",
        "beta_fast": "beta_fast", "beta_slow": "beta_slow",
        "attention_factor": "attention_factor"}
SAMPLED_LAYERS = (0, 2, 4)
# (reading, its limit) of the reference check, for the result line
COMPARED = (("loss_abs_err", "loss_atol"), ("worst", "grad_rtol"),
            ("worst_routed", "grad_rtol_routed"))


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
    for kind, fld in (("full_attention", "rope_full"),
                      ("sliding_attention", "rope_sliding")):
        given = config["rope_parameters"][kind]
        same(f"rope_parameters.{kind}.rope_type", given["rope_type"],
             "yarn" if model[fld].get("yarn_factor") else "default")
        for key, rope_fld in ROPE.items():
            if key in given:
                same(f"rope_parameters.{kind}.{key}", float(given[key]),
                     float(model[fld][rope_fld]))
    same("gating", config["gating"].replace("-", "_"), model["attn_gate"])
    same("gating_types", set(config["gating_types"]), {model["attn_gate"]})
    same("mlp_only_layers", config["mlp_only_layers"],
         [i for i, k in enumerate(model["mlp_layer_types"]) if k == "dense"])
    for key, run in (("tie_word_embeddings", False),
                     ("attention_bias", False),
                     ("moe_router_logit_softcapping", 0),
                     ("moe_apply_router_weight_on_input", False),
                     ("decoder_sparse_step", 1)):
        same(key, config[key], run)


def _leaf(layers, i: int, key: str, *tile):
    """``tile`` of leaf ``key`` of layer ``i`` out of the program's grouped
    ``layers`` tree (``models/transformer.py:group_layers``), one slice."""
    lead, period = layers["lead"], layers["period"]
    if i < len(lead):
        return lead[i][key][tile] if tile else lead[i][key]
    j = i - len(lead)
    return period[j % len(period)][key][(j // len(period), *tile)]


def _picked(leaf, rows):
    """The leaves the check compares: ``leaf(i, key, *tile)`` reads the
    layers, ``leaf(None, key, *tile)`` the tree's top level."""
    s = slice(SAMPLE_ROWS)
    out = {"out_norm": leaf(None, "out_norm"),
           "embed": leaf(None, "embed", rows)}
    for i in SAMPLED_LAYERS:
        out.update({f"L{i}.attn_norm": leaf(i, "attn_norm"),
                    f"L{i}.mlp_norm": leaf(i, "mlp_norm"),
                    f"L{i}.wq": leaf(i, "wq", s, s),
                    f"L{i}.wk": leaf(i, "wk", s, s),
                    f"L{i}.wg": leaf(i, "wg", s)})
        if i == 0:                                  # the dense layer
            out["L0.w2"] = leaf(0, "w2", s, s)
        else:          # every held expert's tile, the shared expert, router
            out.update({f"L{i}.w2": leaf(i, "w2", slice(None), s, s),
                        f"L{i}.shared_w2": leaf(i, "shared_w2", s, s),
                        f"L{i}.router": leaf(i, "router", s)})
    return {k: np.asarray(v, np.float64) for k, v in out.items()}


def _sample(params, rows):
    def leaf(i, key, *tile):
        if i is None:
            return params[key][tile] if tile else params[key]
        return _leaf(params["layers"], i, key, *tile)

    return _picked(leaf, rows)


def _sample_grads(grads, rows):
    def leaf(i, key, *tile):
        tree = grads if i is None else grads["layers"][i]
        return tree[key][tile] if tile else tree[key]

    return _picked(leaf, rows)


def routed(leaf: str) -> bool:
    """Whether a sampled leaf lies on the routed experts' path: a router, or
    the held experts' ``w2`` (layer 0's ``w2`` is a dense one)."""
    return leaf.endswith(".router") or (leaf.endswith(".w2")
                                        and not leaf.startswith("L0."))


def reference_check(trainer, reference, model: dict, tokens: np.ndarray,
                    lr: float, rt) -> dict:
    """One train step on ``tokens`` against the plain reference: the loss,
    and (old - new) / lr of the sampled leaves against its gradient."""
    import jax

    rows = np.unique(tokens)[:SAMPLE_ROWS]
    local = jax.tree_util.tree_map(
        lambda a: a.addressable_shards[0].data, trainer.params)
    toks = jax.device_put(tokens, rt.devices[0])
    ref_loss, ref_grads = reference.loss_and_grads(local, toks, model,
                                                   layers=SAMPLED_LAYERS)
    ref_loss = float(ref_loss)
    want = _sample_grads(ref_grads, rows)
    del ref_grads, local
    before = _sample(trainer.params, rows)
    sys_loss = float(trainer.train_step_async(tokens))
    after = _sample(trainer.params, rows)
    errs = {k: float(np.linalg.norm((before[k] - after[k]) / lr - want[k])
                     / np.linalg.norm(want[k])) for k in want}
    worst = {kind: max(v for k, v in errs.items() if routed(k) == kind)
             for kind in (False, True)}
    out = {"loss_system": sys_loss, "loss_reference": ref_loss,
           "loss_abs_err": abs(sys_loss - ref_loss), "grad_rel_err": errs,
           "worst": worst[False], "worst_routed": worst[True],
           "loss_atol": reference.LOSS_ATOL, "grad_rtol": reference.GRAD_RTOL,
           "grad_rtol_routed": reference.GRAD_RTOL_ROUTED,
           "layers": list(SAMPLED_LAYERS), "shape": list(tokens.shape)}
    out["ok"] = bool(out["loss_abs_err"] <= reference.LOSS_ATOL
                     and worst[False] <= reference.GRAD_RTOL
                     and worst[True] <= reference.GRAD_RTOL_ROUTED
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
        self.model = dict(config["model"])
        self.batch, self.seq = int(traffic["batch"]), int(traffic["seq"])
        axes, shape = traffic["mesh"]["axes"], traffic["mesh"]["shape"]
        if int(np.prod(shape)) != cell.chips:
            raise ValueError(
                f"{cell.name}: mesh {shape} on {cell.chips} chips")
        self.mesh = Mesh(np.asarray(rt.devices).reshape(shape), tuple(axes))
        self.chips = cell.chips
        lr = float(config["trainer"]["learning_rate"])
        self.settle_steps = int(config["trainer"].get("settle_steps", 0))
        # Traces of the attention body by path and of its windows, counted
        # by the program at trace time (parallel/ring_attention.py:
        # _flash_dispatch); read as the change since this session began.
        self._traced = {p: metrics.counter("attention.traced", {"path": p})
                        for p in ("jnp", "mosaic", "interpret")}
        self._traced["window"] = metrics.counter(
            "attention.window_traced",
            {"window": str(self.model["sliding_window"])})
        self._traced_before = {p: c.value for p, c in self._traced.items()}

        t0 = time.perf_counter()
        self.trainer = TransformerTrainer(
            TransformerConfig(**self.model), self.mesh,
            updater_type=config["trainer"]["updater_type"],
            option=AddOption(learning_rate=lr), seed=rt.seed)
        jax.block_until_ready(self.trainer.params)
        init_s = time.perf_counter() - t0

        generator = load_module(cell.search, "generators",
                                traffic["generator"])
        vocab = self.model["vocab_size"]
        self.stream = generator.batches(traffic, vocab, rt.seed)
        check_tokens = next(generator.batches(
            dict(traffic, **traffic["check"]), vocab, rt.seed, stream=1))
        self.place_on = NamedSharding(self.mesh, P(axes[0], None))

        t0 = time.perf_counter()
        reference = load_module(cell.search, "reference", config["reference"])
        self.check = reference_check(self.trainer, reference, self.model,
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
        self.repeated = [float(self.trainer.train_step_async(first))
                         for _ in range(2)]
        warm_s = time.perf_counter() - t0

        # Settling (module docstring): the window's own step, un-timed, on
        # fresh batches.  The counted routes stay on the device.
        t0 = time.perf_counter()
        self.settling = []
        for _ in range(self.settle_steps):
            loss = self.trainer.train_step_async(
                jax.device_put(next(self.stream), self.place_on))
            self.settling.append((loss, self.trainer.routes))
        jax.block_until_ready(self.trainer.params)
        settle_s = time.perf_counter() - t0
        rt.log(setup_parts_s={"trainer_init": init_s,
                              "reference_check": check_s,
                              "compile_or_load": compile_s,
                              "warm_up": warm_s, "settling": settle_s},
               step_peak_bytes=self.peak_bytes,
               repeated_batch_losses=self.repeated)

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
        tokens_per_step = self.batch * self.seq
        finite = [bool(np.isfinite(v)) for v in losses]
        traced = {p: c.value - self._traced_before[p]
                  for p, c in self._traced.items()}

        # [steps, routed layers, held experts + 1], fetched after the window
        counted = np.stack([np.asarray(r) for r in routes]).astype(np.int64)
        held = counted[:, :, :-1]
        routes_per_step = counted.shape[1] * tokens_per_step * self.model[
            "top_k"]
        held_per_step = float(held.sum(axis=(1, 2)).mean())
        per_expert = held.mean(axis=0)               # [layers, held]
        rt.log(steps_in_window=len(done_at), step_s=step_s,
               last_loss=losses[-1], attention_traced=traced,
               settling={"steps": self.settle_steps,
                         "losses_every_8th":
                             [float(l) for l, _ in self.settling[::8]],
                         "held_routes_every_8th":
                             [int(np.asarray(r)[:, :-1].sum())
                              for _, r in self.settling[::8]]},
               held_routes_in_window=held.sum(axis=(1, 2)).tolist(),
               held_routes={"per_step": held_per_step,
                            "of": routes_per_step,
                            "per_layer": held.sum(axis=2).mean(axis=0).tolist(),
                            "expert_max_over_mean":
                                (per_expert.max(axis=1)
                                 / per_expert.mean(axis=1)).tolist()})
        model = self.model
        full, sliding = "full_attention", "sliding_attention"
        full_bytes = flops_laguna.flash_kernel_bytes(model, self.batch,
                                                     self.seq, full)
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
                "no attention on the jnp path": traced["jnp"] == 0,
                "attention traced through the kernel":
                    traced["mosaic"] + traced["interpret"] > 0,
                "windowed attention traced": traced["window"] > 0,
                "every step's routes add up": bool(np.all(
                    counted.sum(axis=2) == tokens_per_step * model["top_k"]))},
            facts={
                "runner": "lm_train_kinds", "chips": self.chips,
                "steps": steps,
                "step_s": step_s["median"], "step_s_mean": step_s["mean"],
                "tokens_per_step": tokens_per_step,
                "held_routes_per_step": held_per_step,
                "routes_per_step": routes_per_step,
                "flops_per_step": flops_laguna.train_flops(
                    model, self.batch, self.seq, held_per_step),
                # what the flash_fwd / flash_bwd readers divide by: the
                # calls of those names are the full-attention layers'
                "attention_flops_per_step": flops_laguna.attention_flops(
                    model, self.batch, self.seq, full),
                "attention_bytes_per_step":
                    full_bytes["fwd"] + full_bytes["bwd"],
                "attention_bwd_bytes_per_step": full_bytes["bwd"],
                "sliding_attention_flops_per_step":
                    flops_laguna.attention_flops(model, self.batch, self.seq,
                                                 sliding),
                "sliding_kernel_bytes_per_step":
                    flops_laguna.flash_kernel_bytes(model, self.batch,
                                                    self.seq, sliding),
                "gmm_held_flops_per_step":
                    flops_laguna.routed_flops(model, held_per_step),
                "gmm_held_bytes_per_step":
                    flops_laguna.grouped_matmul_bytes(model, held_per_step)},
            hlo_texts=self.hlo_texts, compiled_peak_bytes=self.peak_bytes,
            compared=compared(self.check, COMPARED))


def setup(cell, rt) -> Session:
    return Session(cell, rt)
