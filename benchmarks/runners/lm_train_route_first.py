"""Runner ``lm_train_route_first``: language-model training through
``multiverso_tpu.models.TransformerTrainer`` for a configuration whose router
reads the attention sub-layer's own input and chooses before attention runs,
whose experts are ReLU-gated and ALL held, and whose layers are full
attention without a position embedding among windowed, rotated ones over
grouped K/V heads.

``lm_train_kinds`` cannot run such a configuration (its published-key check
holds another family's keys, it samples a shared expert and a leading dense
layer, and it reads a ``[held + 1]`` load a share counts), so this is its
sibling: the same set-up and the same window loop (``lm_train``'s docstring:
trainer, reference check through a step of the sample's shape, the cell's
step compiled with its memory account, two warm-up steps on one batch, then
steps enqueued one ahead on fresh seeded batches, the rate from the median
time between completions, ``step_seconds``), the same ``correct`` checks, and
its own:

- published keys held equal to the ``model`` group (``_check_published``);
- sampled leaves of two layers, one of each kind (``SAMPLED_LAYERS``: the
  unrotated full layer and a windowed one): the norm gains, tiles of ``wq``,
  ``wk``, ``wv``, ``wo``, the router's first rows, every expert's ``w1``,
  ``w3`` and ``w2`` tile; embedding rows and the final norm gain;
- two bounds on the sampled leaves' gradients, the reference's ``GRAD_RTOL``
  for the leaves outside the routed experts' path and ``GRAD_RTOL_ROUTED``
  for the routers and the experts' tiles (``smallthinker_lm.routed``), which
  take every route the program's bfloat16 hidden state sends elsewhere than
  the reference's float32 one;
- **no settling and no counted routes**: every expert is held, so a layer's
  routed work is ``tokens x top_k`` rows whatever the seed's router chose;
- facts from ``benchmarks/flops_smallthinker.py``, and which backward the
  flash kernels traced (``attention.bwd_traced{path=}``).

``python -m benchmarks.runners.lm_train_route_first --seeds 41,42`` (on the
chip) prints, a seed, what the check reads of the program and what it would
read were the reference each of ``CONTROLS`` (a lower precision, or another
mathematics): the readings the limits were set between.  Two seeds fit one
process and a third does not (the jitted steps keep their trainers, 6.3 GiB of
weights each, alive): give a third seed a process of its own.
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks import flops_smallthinker as counts
from benchmarks.harness import (Measured, compared, compiled_peak_bytes,
                                load_module)
from benchmarks.runners.lm_train import (LOSSES_LOGGED, SAMPLE_ROWS,
                                         step_seconds)
from benchmarks.runners.lm_train_kinds import _leaf

# Published config keys and the program's field for each.
PUBLISHED = {"hidden_size": "dim", "num_attention_heads": "n_heads",
             "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
             "moe_ffn_hidden_size": "hidden",
             "moe_num_primary_experts": "num_experts",
             "moe_num_active_primary_experts": "top_k",
             "norm_topk_prob": "norm_topk_prob",
             "sliding_window_size": "sliding_window",
             "vocab_size": "vocab_size", "num_hidden_layers": "n_layers",
             "rms_norm_eps": "norm_eps",
             "max_position_embeddings": "max_seq"}
NOPE, SLIDING = counts.NOPE, counts.SLIDING
SAMPLED_LAYERS = (0, 1)
# (reading, its limit) of the reference check, for the result line
COMPARED = (("loss_abs_err", "loss_atol"), ("worst", "grad_rtol"),
            ("worst_routed", "grad_rtol_routed"))
# What the limits have to refuse, as the reference's ``control=``.
CONTROLS = {
    "router_reads_ffn_input": {"router_input": "mlp"},
    "silu_for_relu": {"act": "silu"},
    "rotary_on_full_layer": {"rope_full": True},
    "band_as_full_causal": {"window_off": True},
    "routing_bf16": {"routing_dtype": "bfloat16"},
    "softmax_bf16": {"softmax_dtype": "bfloat16"},
    "weights_float8": {"weights_dtype": "float8_e4m3fn"},
}


def _check_published(config: dict) -> None:
    model, name = config["model"], config["name"]

    def same(what, published, run):
        if published != run:
            raise ValueError(f"{name}: {what}={published!r} but the model "
                             f"group runs {run!r}")

    for key, fld in PUBLISHED.items():
        same(key, config[key], model[fld])
    # the two lists say a layer's kind between them: 0 0 = full attention
    # without a position embedding, 1 1 = windowed and rotated
    kind_of = {(0, 0): NOPE, (1, 1): SLIDING}
    same("rope_layout / sliding_window_layout",
         [kind_of.get(pair) for pair in zip(config["rope_layout"],
                                            config["sliding_window_layout"])],
         list(model["layer_types"]))
    same("rope_theta", float(config["rope_theta"]),
         float(model["rope_sliding"]["theta"]))
    same("rope_scaling", config["rope_scaling"], None)
    same("rotary_factor", 1.0, float(model["rope_sliding"].get(
        "rotary_factor", 1.0)))
    for key, run in (("tie_word_embeddings", False),
                     ("moe_primary_router_apply_softmax", True)):
        same(key, config[key], run)
    for fld, run in (("router_input", "attn"), ("ffn_act", "relu"),
                     ("aux_loss_coef", 0.0), ("router_z_loss_coef", 0.0)):
        same(f"model.{fld}", model[fld], run)
    same("model.experts_held", model.get("experts_held", 0), 0)


def _picked(leaf, rows):
    """The leaves the check compares: ``leaf(i, key, *tile)`` reads the
    layers, ``leaf(None, key, *tile)`` the tree's top level."""
    s, every = slice(SAMPLE_ROWS), slice(None)
    out = {"out_norm": leaf(None, "out_norm"),
           "embed": leaf(None, "embed", rows)}
    for i in SAMPLED_LAYERS:
        out.update({f"L{i}.attn_norm": leaf(i, "attn_norm"),
                    f"L{i}.mlp_norm": leaf(i, "mlp_norm"),
                    f"L{i}.router": leaf(i, "router", s)})
        out.update({f"L{i}.{key}": leaf(i, key, s, s)
                    for key in ("wq", "wk", "wv", "wo")})
        out.update({f"L{i}.{key}": leaf(i, key, every, s, s)
                    for key in ("w1", "w3", "w2")})       # every expert's
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


def _control(spec: dict) -> dict:
    """A control of ``CONTROLS`` as the reference takes it: dtypes by name."""
    import jax.numpy as jnp

    return {k: getattr(jnp, v) if k.endswith("_dtype") else v
            for k, v in spec.items()}


def _read(reference, sys_loss, moved, ref_loss, want) -> dict:
    """What the check reads of the program's step (its loss, ``moved`` =
    (old - new) / lr of the sampled leaves) against one reference's loss and
    gradients ``want``."""
    errs = {k: float(np.linalg.norm(moved[k] - want[k])
                     / np.linalg.norm(want[k])) for k in want}
    worst = {kind: max(v for k, v in errs.items()
                       if reference.routed(k) == kind)
             for kind in (False, True)}
    out = {"loss_system": sys_loss, "loss_reference": ref_loss,
           "loss_abs_err": abs(sys_loss - ref_loss), "grad_rel_err": errs,
           "worst": worst[False], "worst_routed": worst[True]}
    out["ok"] = bool(out["loss_abs_err"] <= reference.LOSS_ATOL
                     and worst[False] <= reference.GRAD_RTOL
                     and worst[True] <= reference.GRAD_RTOL_ROUTED
                     and all(np.isfinite(v) for v in errs.values()))
    return out


def reference_check(trainer, reference, model: dict, tokens: np.ndarray,
                    lr: float, rt, controls=()) -> dict:
    """One train step on ``tokens`` against the plain reference: the loss,
    and (old - new) / lr of the sampled leaves against its gradient.  With
    ``controls`` (names of ``CONTROLS``) also what the same step reads
    against the reference under each, ``out["controls"][name]``."""
    import jax

    rows = np.unique(tokens)[:SAMPLE_ROWS]
    local = jax.tree_util.tree_map(
        lambda a: a.addressable_shards[0].data, trainer.params)
    toks = jax.device_put(tokens, rt.devices[0])

    def wanted(control):
        ref_loss, ref_grads = reference.loss_and_grads(
            local, toks, model, layers=SAMPLED_LAYERS, control=control)
        return float(ref_loss), _sample_grads(ref_grads, rows)

    plain = wanted(None)
    under = {name: wanted(_control(CONTROLS[name]))
             for name in controls}
    del local
    before = _sample(trainer.params, rows)
    sys_loss = float(trainer.train_step_async(tokens))
    after = _sample(trainer.params, rows)
    moved = {k: (before[k] - after[k]) / lr for k in before}
    out = _read(reference, sys_loss, moved, *plain)
    out.update(loss_atol=reference.LOSS_ATOL, grad_rtol=reference.GRAD_RTOL,
               grad_rtol_routed=reference.GRAD_RTOL_ROUTED,
               layers=list(SAMPLED_LAYERS), shape=list(tokens.shape))
    if controls:
        out["controls"] = {name: _read(reference, sys_loss, moved, *found)
                           for name, found in under.items()}
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
        # What the program counted at trace time (docs/observability.md),
        # read as the change since this session began.
        self._traced = {p: metrics.counter("attention.traced", {"path": p})
                        for p in ("jnp", "mosaic", "interpret")}
        self._traced.update(
            window=metrics.counter("attention.window_traced",
                                   {"window": str(model["sliding_window"])}),
            nope=metrics.counter("attention.nope_traced",
                                 {"heads": str(model["n_heads"])}),
            route_early=metrics.counter(
                "moe.traced", {"dispatch": model["moe_dispatch"],
                               "act": model["ffn_act"],
                               "router_input": model["router_input"]}),
            bwd_fused=metrics.counter("attention.bwd_traced",
                                      {"path": "fused"}),
            bwd_split=metrics.counter("attention.bwd_traced",
                                      {"path": "split"}))
        self._traced_before = {p: c.value for p, c in self._traced.items()}

        t0 = time.perf_counter()
        self.trainer = TransformerTrainer(
            TransformerConfig(**model), self.mesh,
            updater_type=config["trainer"]["updater_type"],
            option=AddOption(learning_rate=lr), seed=rt.seed)
        jax.block_until_ready(self.trainer.params)
        init_s = time.perf_counter() - t0
        self.parameters = sum(
            int(np.prod(leaf.shape))
            for leaf in jax.tree_util.tree_leaves(self.trainer.params))

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
        self.repeated = [float(self.trainer.train_step_async(first))
                         for _ in range(2)]
        warm_s = time.perf_counter() - t0
        rt.log(setup_parts_s={"trainer_init": init_s,
                              "reference_check": check_s,
                              "compile_or_load": compile_s,
                              "warm_up": warm_s},
               parameters=self.parameters, step_peak_bytes=self.peak_bytes,
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
        tokens_per_step = self.batch * self.seq
        finite = [bool(np.isfinite(v)) for v in losses]
        traced = {p: c.value - self._traced_before[p]
                  for p, c in self._traced.items()}
        rt.log(steps_in_window=len(done_at), step_s=step_s,
               last_loss=losses[-1], traced=traced)
        model, batch, seq = self.model, self.batch, self.seq

        def need(attn, which):
            """What one pass of the layers of kind ``attn`` requires."""
            return {"flops": counts.attention_flops(model, batch, seq, attn,
                                                    which),
                    "bytes": counts.flash_bytes(model, batch, seq,
                                                attn)[which]}

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
                "attention without rotary traced": traced["nope"] > 0,
                "the route made before attention": traced["route_early"] > 0},
            facts={
                "runner": "lm_train_route_first", "chips": self.chips,
                "steps": steps, "parameters": self.parameters,
                "step_s": step_s["median"], "step_s_mean": step_s["mean"],
                "tokens_per_step": tokens_per_step,
                "flops_per_step": counts.train_flops(model, batch, seq),
                "flash_bwd_traced": {"fused": traced["bwd_fused"],
                                     "split": traced["bwd_split"]},
                "full_fwd_per_step": need(NOPE, "fwd"),
                "full_bwd_per_step": need(NOPE, "bwd"),
                "win_fwd_per_step": need(SLIDING, "fwd"),
                "win_bwd_per_step": need(SLIDING, "bwd"),
                "gmm_per_step": {
                    "flops": counts.routed_flops(model, tokens_per_step),
                    "bytes": counts.grouped_matmul_bytes(model,
                                                         tokens_per_step)}},
            hlo_texts=self.hlo_texts, compiled_peak_bytes=self.peak_bytes,
            compared=compared(self.check, COMPARED))


def setup(cell, rt) -> Session:
    return Session(cell, rt)


def main(argv=None) -> int:
    """The readings the reference's limits were set between, a seed: the
    program's, and each control's (module docstring)."""
    import argparse
    import json

    from benchmarks import harness

    ap = argparse.ArgumentParser(prog="lm_train_route_first")
    ap.add_argument("--seeds", default="41")
    ap.add_argument("--controls", default=",".join(CONTROLS))
    ap.add_argument(
        "--workload",
        default="smallthinker-21b-a3b-l4-e64.zipf-seq16k-b1-chk8k")
    ap.add_argument("--learning-rate", type=float, default=0.0)
    args = ap.parse_args(argv)
    from jax.sharding import Mesh

    from multiverso_tpu import compile_cache
    from multiverso_tpu.models import TransformerConfig, TransformerTrainer
    from multiverso_tpu.updaters import AddOption

    compile_cache.configure()
    cell = harness.load_cell(args.workload)
    devices = harness.require_tpu(cell.chips)
    rt = harness.Runtime(seed=0, seconds=0.0, trace=False,
                         t_start=time.perf_counter(), devices=list(devices))
    config, traffic = cell.config, cell.traffic
    model = dict(config["model"])
    lr = args.learning_rate or float(config["trainer"]["learning_rate"])
    reference = load_module(cell.search, "reference", config["reference"])
    generator = load_module(cell.search, "generators", traffic["generator"])
    mesh = Mesh(np.asarray(devices).reshape(traffic["mesh"]["shape"]),
                tuple(traffic["mesh"]["axes"]))
    for seed in (int(s) for s in args.seeds.split(",")):
        trainer = TransformerTrainer(
            TransformerConfig(**model), mesh,
            updater_type=config["trainer"]["updater_type"],
            option=AddOption(learning_rate=lr), seed=seed)
        tokens = next(generator.batches(dict(traffic, **traffic["check"]),
                                        model["vocab_size"], seed, stream=1))
        t0 = time.perf_counter()
        found = reference_check(
            trainer, reference, model, tokens, lr, rt,
            [c for c in args.controls.split(",") if c])
        print(json.dumps({"seed": seed, "learning_rate": lr,
                          "seconds": time.perf_counter() - t0,
                          "check": found}), flush=True)
        del trainer
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
