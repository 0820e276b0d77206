"""Runner ``lm_train``: language-model training through
``multiverso_tpu.models.TransformerTrainer``, the way the flagship runs.

The configuration file's ``model`` group goes to ``TransformerConfig``
unread, so a field a later PR adds to the program needs no edit here; the
published keys beside it (``hidden_size`` ...) are held equal to it.  The
traffic file gives the step's batch and sequence, the mesh, and the shape of
the reference check.

Set-up: trainer (the program's own host-side init), the reference check on
a seeded sample through a step of the sample's shape, the cell's step
compiled (or loaded from the compile cache) with the compiler's memory
account read off it, two warm-up steps on one batch (uniform random tokens
carry nothing to learn, so fresh batches keep the loss within its noise: the
falling loss ``correct`` asks for is shown on the repeated batch).  Window:
steps enqueued with ``train_step_async`` one ahead; each loss is fetched
after the next step is enqueued, so the fetch never idles the device; every
step trains on a fresh seeded batch made and placed inside the loop.  The
rate is tokens a step over the *median* time between two completions
(``step_seconds``): a stall of the shared host, or a few slow steps, lengthens
the window and not the median, and what the median leaves out is reported
beside it (``host.stall_share``).
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks import flops
from benchmarks.harness import (Measured, compared, compiled_peak_bytes,
                                load_module)

# Published config keys the program has a field for.
PUBLISHED = {"hidden_size": "dim", "num_attention_heads": "n_heads",
             "intermediate_size": "hidden", "vocab_size": "vocab_size",
             "num_hidden_layers": "n_layers", "rope_theta": "rope_theta",
             "rms_norm_eps": "norm_eps", "max_position_embeddings": "max_seq"}
LOSSES_LOGGED = 20
SAMPLE_ROWS = 256          # embedding rows and weight-tile edge sampled
# (reading, its limit) of the reference check, for the result line
COMPARED = (("loss_abs_err", "loss_atol"), ("grad_rel_err", "grad_rtol"))


def _check_published(config: dict) -> None:
    model = config["model"]
    for key, fld in PUBLISHED.items():
        if key in config and config[key] != model[fld]:
            raise ValueError(f"{config['name']}: {key}={config[key]} but "
                             f"model.{fld}={model[fld]}")
    if "head_dim" in config and (config["head_dim"] * model["n_heads"]
                                 != model["dim"]):
        raise ValueError(f"{config['name']}: head_dim x heads != dim")
    if config.get("num_key_value_heads",
                  model["n_heads"]) != model["n_heads"]:
        raise ValueError("the program's attention is multi-head only")
    if config.get("tie_word_embeddings", False):
        raise ValueError("the program's output head is untied")


def step_seconds(done_at) -> dict:
    """Seconds a step from the completion times of consecutive steps: the
    median gap, which a stall in a few steps does not move, with the mean
    (window / steps) and the spread beside it."""
    gaps = np.diff(np.asarray(done_at, np.float64))
    q25, median, q75 = (float(v) for v in np.percentile(gaps, [25, 50, 75]))
    return {"median": median, "mean": float(gaps.mean()), "q25": q25,
            "q75": q75, "max": float(gaps.max())}


def _sample(params, layer: int, rows):
    """The leaves the check compares, as host arrays: final norm gain, one
    layer's norm gains, some embedding rows, a tile of a wq and of a w2."""
    lyr = params["layers"]

    def leaf(k, *tile):
        # leaves stacked [L, ...] (scan), or a list of per-layer dicts
        if isinstance(lyr, dict):
            return lyr[k][(layer, *tile)]
        return lyr[layer][k][tile] if tile else lyr[layer][k]

    tile = (slice(SAMPLE_ROWS), slice(SAMPLE_ROWS))
    picked = {"out_norm": params["out_norm"], "embed": params["embed"][rows],
              "attn_norm": leaf("attn_norm"), "mlp_norm": leaf("mlp_norm"),
              "wq": leaf("wq", *tile), "w2": leaf("w2", *tile)}
    return {k: np.asarray(v, np.float64) for k, v in picked.items()}


def reference_check(trainer, reference, model: dict, tokens: np.ndarray,
                    lr: float, rt) -> dict:
    """One train step on ``tokens`` against the plain reference: the loss,
    and (old - new) / lr of the sampled leaves against its gradient."""
    import jax

    layer = model["n_layers"] // 2
    rows = np.unique(tokens)[:SAMPLE_ROWS]
    # The reference runs on the first chip, on that chip's copy of the
    # (replicated) parameters: no second copy is made.
    local = jax.tree_util.tree_map(
        lambda a: a.addressable_shards[0].data, trainer.params)
    toks = jax.device_put(tokens, rt.devices[0])
    ref_loss, ref_grads = reference.loss_and_grads(local, toks, model, layer)
    ref_loss = float(ref_loss)
    s = SAMPLE_ROWS
    want = {"out_norm": ref_grads["out_norm"],
            "embed": ref_grads["embed"][rows],
            "attn_norm": ref_grads["layer"]["attn_norm"],
            "mlp_norm": ref_grads["layer"]["mlp_norm"],
            "wq": ref_grads["layer"]["wq"][:s, :s],
            "w2": ref_grads["layer"]["w2"][:s, :s]}
    want = {k: np.asarray(v, np.float64) for k, v in want.items()}
    del ref_grads, local
    before = _sample(trainer.params, layer, rows)
    sys_loss = float(trainer.train_step_async(tokens))
    after = _sample(trainer.params, layer, rows)
    errs = {k: float(np.linalg.norm((before[k] - after[k]) / lr - want[k])
                     / np.linalg.norm(want[k])) for k in want}
    out = {"loss_system": sys_loss, "loss_reference": ref_loss,
           "loss_abs_err": abs(sys_loss - ref_loss), "grad_rel_err": errs,
           "loss_atol": reference.LOSS_ATOL, "grad_rtol": reference.GRAD_RTOL,
           "layer": layer, "shape": list(tokens.shape)}
    out["ok"] = bool(out["loss_abs_err"] <= reference.LOSS_ATOL
                     and max(errs.values()) <= reference.GRAD_RTOL
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
        # Traces of the attention body by path, counted by the program at
        # trace time (parallel/ring_attention.py:_flash_dispatch); read as
        # the change since this session began.
        self._traced = {p: metrics.counter("attention.traced", {"path": p})
                        for p in ("jnp", "mosaic", "interpret")}
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

        # The cell's own step: compiled here (or loaded from the compile
        # cache), so its memory account and text are at hand; the first
        # train_step_async below finds the same program in the cache.
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
               step_peak_bytes=self.peak_bytes,
               repeated_batch_losses=self.repeated)

    def measure(self, rt) -> Measured:
        import jax

        trainer, stream, span = self.trainer, self.stream, rt.span
        done_at, window_losses = [], []
        # One step is already in flight when the window opens, so the
        # device is busy from its first instant.
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
        head_dim = self.model["dim"] // self.model["n_heads"]
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
                    bool(self.repeated[1] < self.repeated[0]),
                "no attention on the jnp path": traced["jnp"] == 0,
                "attention traced through the kernel":
                    traced["mosaic"] + traced["interpret"] > 0},
            facts={
                "runner": "lm_train", "chips": self.chips, "steps": steps,
                "step_s": step_s["median"], "step_s_mean": step_s["mean"],
                "tokens_per_step": tokens_per_step,
                "flops_per_step": flops.lm_train_flops(
                    self.model, self.batch, self.seq),
                "attention_flops_per_step":
                    self.model["n_layers"] * flops.causal_attention_flops(
                        self.batch, self.model["n_heads"], self.seq,
                        head_dim),
                "attention_bytes_per_step":
                    self.model["n_layers"] * flops.flash_kernel_bytes(
                        self.batch, self.model["n_heads"], self.seq,
                        head_dim),
                "attention_bwd_bytes_per_step":
                    self.model["n_layers"] * flops.flash_backward_bytes(
                        self.batch, self.model["n_heads"], self.seq,
                        head_dim)},
            hlo_texts=self.hlo_texts, compiled_peak_bytes=self.peak_bytes,
            compared=compared(self.check, COMPARED))


def setup(cell, rt) -> Session:
    return Session(cell, rt)
