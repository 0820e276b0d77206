"""Runner ``sgns_train``: word2vec (skip-gram, negative sampling) through
the entry point users call, ``SkipGram.train_epoch_fused``: the app's own
pure-Python pair generator feeding the compiled gather -> gradients ->
scatter-apply step over two ``MatrixTable``s.

Set-up: ``mv.init`` on the cell's chips, the tables (the program's own
host-side init), the seeded corpus, the reference check through
``train_epoch_fused`` on a short slice (which also compiles the step), the
compiler's memory account of the step.  Window: consecutive chunks of the
corpus, a fixed number of tokens each, one ``train_epoch_fused`` call a
chunk; the metric is the median over chunks of pairs trained per second.
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks import flops
from benchmarks.harness import (Measured, compared, compiled_peak_bytes,
                                load_module)

LOSSES_LOGGED = 20
# (reading, its limit) of the reference check, for the result line
COMPARED = (("loss_rel_err", "loss_rtol"), ("row_err_in", "row_rtol"),
            ("row_err_out", "row_rtol"))


def reference_check(skipgram, reference, tokens: np.ndarray, batch: int,
                    lr: float, seed: int) -> dict:
    """``train_epoch_fused`` on ``tokens`` against the numpy reference fed
    the same batches: the last loss, and every row either table touched,
    read back through ``get_rows``."""
    batches = list(skipgram.batches(tokens, batch, seed=seed))
    if not batches:
        raise ValueError(f"check slice of {tokens.shape[0]} tokens gives no "
                         f"batch of {batch} pairs")
    ids_in = np.unique(np.concatenate([c for c, _, _ in batches]))
    ids_out = np.unique(np.concatenate(
        [np.concatenate([o, n.reshape(-1)]) for _, o, n in batches]))
    w_in = skipgram.table_in.get_rows(ids_in).astype(np.float64)
    w_out = skipgram.table_out.get_rows(ids_out).astype(np.float64)
    steps, sys_loss = skipgram.train_epoch_fused(tokens, batch, seed=seed)
    for c, o, n in batches:
        ref_loss = reference.step(
            w_in, w_out, np.searchsorted(ids_in, c),
            np.searchsorted(ids_out, o), np.searchsorted(ids_out, n), lr)
    got_in = skipgram.table_in.get_rows(ids_in)
    got_out = skipgram.table_out.get_rows(ids_out)
    err_in = float(np.max(np.abs(got_in - w_in)) / np.max(np.abs(w_in)))
    err_out = float(np.max(np.abs(got_out - w_out)) / np.max(np.abs(w_out)))
    counts = np.bincount(np.concatenate([c for c, _, _ in batches]))
    out = {"steps": int(steps), "loss_system": float(sys_loss),
           "loss_reference": float(ref_loss),
           "loss_rel_err": abs(sys_loss - ref_loss) / abs(ref_loss),
           "rows_in": int(ids_in.size), "rows_out": int(ids_out.size),
           "most_repeated_id_count": int(counts.max()),
           "row_err_in": err_in, "row_err_out": err_out,
           "loss_rtol": reference.LOSS_RTOL, "row_rtol": reference.ROW_RTOL}
    out["ok"] = bool(steps == len(batches)
                     and out["loss_rel_err"] <= reference.LOSS_RTOL
                     and max(err_in, err_out) <= reference.ROW_RTOL)
    return out


class Session:
    def __init__(self, cell, rt):
        import jax
        from jax.sharding import Mesh

        import multiverso_tpu as mv
        from multiverso_tpu.apps import SkipGram

        config, traffic = cell.config, cell.traffic
        self.chips = cell.chips
        self.batch = int(traffic["batch_pairs"])
        self.negatives = int(config["negatives"])
        self.dim = int(config["dim"])
        lr = float(config["learning_rate_per_pair"]) * self.batch
        self.mv = mv
        mv.init(args=[f"-updater_type={config['updater_type']}",
                      "-sync=false", "-log_level=error"],
                mesh=Mesh(np.asarray(rt.devices), ("worker",)))
        t0 = time.perf_counter()
        self.skipgram = SkipGram(
            int(config["vocab_size"]), self.dim, learning_rate=lr,
            negatives=self.negatives, window=int(config["window"]),
            updater_type=config["updater_type"], name="bench_w2v",
            seed=rt.seed)
        for table in (self.skipgram.table_in, self.skipgram.table_out):
            jax.block_until_ready(table.raw_value()[0])
        init_s = time.perf_counter() - t0

        t0 = time.perf_counter()
        generator = load_module(cell.search, "generators",
                                traffic["generator"])
        tokens = generator.corpus(traffic, int(config["vocab_size"]), rt.seed)
        n_check = int(traffic["check_tokens"])
        self.chunks = generator.chunks(tokens[n_check:],
                                       int(traffic["chunk_tokens"]))
        corpus_s = time.perf_counter() - t0

        t0 = time.perf_counter()
        reference = load_module(cell.search, "reference", config["reference"])
        self.check = reference_check(self.skipgram, reference,
                                     tokens[:n_check], self.batch, lr,
                                     rt.seed)
        check_s = time.perf_counter() - t0
        rt.log(reference_check=self.check)

        t0 = time.perf_counter()
        step, place = self.skipgram.make_fused_step()
        din, sin = self.skipgram.table_in.raw_value()
        dout, sout = self.skipgram.table_out.raw_value()
        ids = place(np.zeros(self.batch, np.int32))
        neg = place(np.zeros((self.batch, self.negatives), np.int32))
        compiled = step.lower(din, sin, dout, sout, ids, ids, neg).compile()
        self.peak_bytes = compiled_peak_bytes(compiled)
        self.hlo_texts = [compiled.as_text()] if rt.trace else []
        del compiled, din, sin, dout, sout
        account_s = time.perf_counter() - t0
        rt.log(setup_parts_s={"tables_init": init_s, "corpus": corpus_s,
                              "reference_check": check_s,
                              "memory_account": account_s},
               step_peak_bytes=self.peak_bytes)

    def measure(self, rt) -> Measured:
        span = rt.span
        records = []                          # (steps, seconds, loss)
        t_open = rt.open_window()
        with span("bench.window"):
            k = 0
            while time.perf_counter() - t_open < rt.seconds:
                tokens = next(self.chunks)
                t0 = time.perf_counter()
                with span("bench.epoch_chunk"):
                    steps, loss = self.skipgram.train_epoch_fused(
                        tokens, self.batch, seed=rt.seed + 1 + k)
                records.append((steps, time.perf_counter() - t0, loss))
                k += 1
        rt.close_window()

        facts = {"runner": "sgns_train", "chips": self.chips,
                 "steps": sum(s for s, _, _ in records),
                 "window_s": sum(t for _, t, _ in records)}
        self.mv.shutdown()

        losses = [loss for _, _, loss in records]
        rates = [s * self.batch / t for s, t, _ in records]
        rt.log(losses_first=losses[:LOSSES_LOGGED], chunks=len(records),
               steps_per_chunk=records[0][0],
               chunk_s_median=float(np.median([t for _, t, _ in records])),
               least_step_bytes=flops.sgns_step_bytes(
                   self.batch, self.negatives, self.dim))
        return Measured(
            attempted=facts["steps"],
            failed=sum(s for s, _, loss in records if not np.isfinite(loss)),
            end_to_end={
                "pairs_per_chip_s": float(np.median(rates)) / self.chips},
            checks={"reference agrees": self.check["ok"],
                    "losses finite": bool(np.all(np.isfinite(losses))),
                    "loss fell": bool(losses[-1] < losses[0])},
            facts=facts, hlo_texts=self.hlo_texts,
            compiled_peak_bytes=self.peak_bytes,
            compared=compared(self.check, COMPARED))


def setup(cell, rt) -> Session:
    return Session(cell, rt)
