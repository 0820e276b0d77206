"""Distributed word embedding (skip-gram negative sampling).

Reference (SURVEY.md §2.36, ``Microsoft/distributed_word_embedding`` linking
libmultiverso): embeddings live in (Sparse)MatrixTables row-sharded over
servers; workers pull the rows a batch touches (`Get(rows)`), compute SGNS
gradients locally, and push row deltas (`Add(rows)`), with an AsyncBuffer
overlapping the next pull with compute.

TPU-native: both embedding matrices are row-sharded ``jax.Array`` tables.
The fused step compiles the whole pull→grad→push round-trip into one XLA
program: gathers fetch rows over ICI, autodiff produces the row gradients,
and the updater scatter-applies them on the rows' home shards.  Row batches
are static-shaped; negatives are pre-sampled on host (the reference samples
on the worker too).
"""

from __future__ import annotations

from functools import partial
from typing import Iterator, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import dashboard, tracing
from ..core import context as core_context
from ..tables import MatrixTable
from ..updaters import AddOption
from ..util import AsyncBuffer

__all__ = ["SkipGram", "synthetic_corpus"]

# Tokens whose pairs ``SkipGram.batches`` expands in one numpy pass.
_EXPAND_TOKENS = 1024


def synthetic_corpus(num_tokens: int, vocab_size: int, seed: int = 0,
                     zipf_a: float = 1.1) -> np.ndarray:
    """Zipf-distributed token stream (text8 stand-in; no dataset egress)."""
    rng = np.random.RandomState(seed)
    ranks = rng.zipf(zipf_a, size=num_tokens)
    return ((ranks - 1) % vocab_size).astype(np.int32)


def _sgns_loss(vc: jax.Array, uo: jax.Array, un: jax.Array) -> jax.Array:
    """Skip-gram negative-sampling loss.

    ``vc`` [B,D] center (input) embeddings, ``uo`` [B,D] positive context
    (output) embeddings, ``un`` [B,K,D] negative samples.
    """
    pos = jnp.einsum("bd,bd->b", vc, uo)
    neg = jnp.einsum("bd,bkd->bk", vc, un)
    return -(jnp.sum(jax.nn.log_sigmoid(pos))
             + jnp.sum(jax.nn.log_sigmoid(-neg))) / vc.shape[0]


class SkipGram:
    """Word2vec SGNS over two row-sharded MatrixTables."""

    def __init__(self, vocab_size: int, dim: int,
                 learning_rate: float = 0.025,
                 negatives: int = 5,
                 window: int = 5,
                 updater_type: str = "sgd",
                 name: str = "w2v",
                 seed: int = 0):
        self.vocab_size = int(vocab_size)
        self.dim = int(dim)
        self.negatives = int(negatives)
        self.window = int(window)
        self.option = AddOption(learning_rate=learning_rate)
        rng = np.random.RandomState(seed)
        with dashboard.monitor("SkipGram::init_draw"):
            init_in = ((rng.rand(vocab_size, dim) - 0.5)
                       / dim).astype(np.float32)
        self.table_in = MatrixTable(vocab_size, dim, init=init_in,
                                    updater_type=updater_type,
                                    name=f"{name}_in",
                                    default_option=self.option)
        self.table_out = MatrixTable(vocab_size, dim,
                                     updater_type=updater_type,
                                     name=f"{name}_out",
                                     default_option=self.option)
        self._rng = np.random.RandomState(seed + 1)
        self._grad_fn = jax.jit(jax.grad(
            lambda vc, uo, un: _sgns_loss(vc, uo, un), argnums=(0, 1, 2)))
        self._fused_cache = {}

    # ------------------------------------------------------------- batching
    @staticmethod
    def _pair_streams(seed: int):
        """The two independent streams of one ``batches`` call, both children
        of ``seed``: the windows' and the negatives'."""
        return tuple(np.random.default_rng([k, seed]) for k in (0, 1))

    def _draw_windows(self, rng: np.random.Generator,
                      count: int) -> np.ndarray:
        """The next ``count`` positions' windows, uniform on 1..window.
        The same stream whatever the counts it is asked for in."""
        return rng.integers(1, self.window + 1, size=count)

    def _expand(self, corpus: np.ndarray, start: int,
                windows: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """Every (corpus[i], corpus[j]) with 0 < |i - j| <= windows[i - start]
        inside the corpus, for i from ``start``, ordered by i, then by j."""
        stop = start + windows.shape[0]
        off = np.arange(-self.window, self.window)
        off += off >= 0                 # -window..-1, 1..window
        j = np.arange(start, stop)[:, None] + off
        keep = (np.abs(off) <= windows[:, None]) & (j >= 0) \
            & (j < corpus.shape[0])
        # Boolean indexing reads row-major: by i, then by j ascending.
        centers = np.broadcast_to(corpus[start:stop, None], keep.shape)[keep]
        return (centers.astype(np.int32, copy=False),
                corpus[j[keep]].astype(np.int32, copy=False))

    def batches(self, corpus: np.ndarray, batch_size: int,
                seed: int = 0) -> Iterator[Tuple[np.ndarray, np.ndarray,
                                                 np.ndarray]]:
        """Static-shaped (centers [B], contexts [B], negatives [B,K]).

        Pairs are expanded ``_EXPAND_TOKENS`` tokens at a time and handed
        out as slices; what a block leaves over is carried into the next,
        and the corpus's last partial batch is dropped.
        """
        corpus = np.asarray(corpus)
        win_rng, neg_rng = self._pair_streams(seed)
        n = corpus.shape[0]
        centers = contexts = np.empty(0, np.int32)
        for start in range(0, n, _EXPAND_TOKENS):
            count = min(_EXPAND_TOKENS, n - start)
            with tracing.span("mv.sgns.expand", tokens=count):
                c, o = self._expand(corpus, start,
                                    self._draw_windows(win_rng, count))
                centers = np.concatenate([centers, c])
                contexts = np.concatenate([contexts, o])
            full = centers.shape[0] - centers.shape[0] % batch_size
            for a in range(0, full, batch_size):
                neg = neg_rng.integers(self.vocab_size, dtype=np.int32,
                                       size=(batch_size, self.negatives))
                yield (centers[a:a + batch_size],
                       contexts[a:a + batch_size], neg)
            centers, contexts = centers[full:], contexts[full:]

    # ------------------------------------------------ parity push-pull path
    def train_batch(self, centers: np.ndarray, contexts: np.ndarray,
                    negatives: np.ndarray) -> None:
        """Reference loop body: Get(rows) → local grads → Add(rows)."""
        B, K = negatives.shape
        vc = jnp.asarray(self.table_in.get_rows(centers))
        out_rows = np.concatenate([contexts, negatives.reshape(-1)])
        out_emb = self.table_out.get_rows(out_rows)
        uo = jnp.asarray(out_emb[:B])
        un = jnp.asarray(out_emb[B:]).reshape(B, K, self.dim)
        dvc, duo, dun = self._grad_fn(vc, uo, un)
        self.table_in.add_rows(centers, np.asarray(dvc), option=self.option)
        self.table_out.add_rows(
            out_rows,
            np.concatenate([np.asarray(duo),
                            np.asarray(dun).reshape(B * K, self.dim)]),
            option=self.option)

    def train_epoch(self, corpus: np.ndarray, batch_size: int,
                    seed: int = 0, prefetch: bool = True) -> int:
        """Parity epoch with AsyncBuffer overlapping batch prep (§2.24)."""
        it = self.batches(corpus, batch_size, seed=seed)
        steps = 0
        if not prefetch:
            for c, o, neg in it:
                self.train_batch(c, o, neg)
                steps += 1
        else:
            with AsyncBuffer(lambda: next(it, None)) as buf:
                while True:
                    batch = buf.get()
                    if batch is None:
                        break
                    self.train_batch(*batch)
                    steps += 1
        if steps == 0:
            raise ValueError(
                f"corpus of {corpus.shape[0]} tokens produced no full batch "
                f"of {batch_size} pairs (partial batches are dropped for "
                "static shapes)")
        return steps

    # ------------------------------------------------------ fused SPMD path
    def make_fused_step(self, batch_axis: str = "worker"):
        """One XLA program: gather rows, SGNS grads, scatter-apply updater.

        Index batches are sharded over the mesh's worker axis; the gathers
        and the scatter-adds cross shards over ICI exactly where the
        reference crossed the network.  Returns
        ``step(din, sin, dout, sout, c, o, neg) -> (din, sin, dout, sout, loss)``
        and a placer for the index arrays.
        """
        cached = self._fused_cache.get(batch_axis)
        if cached is not None:  # reuse: a fresh jit wrapper would recompile
            return cached
        ctx = core_context.get_context()
        from ..parallel.sharding import batch_placer
        _, place = batch_placer(ctx.mesh, batch_axis, dtype=jnp.int32)
        upd_in = self.table_in.updater
        upd_out = self.table_out.updater
        opt = self.option

        from ..updaters.base import scatter_apply

        @partial(jax.jit, donate_argnums=(0, 1, 2, 3))
        def step(din, sin, dout, sout, c, o, neg):
            B, K = neg.shape
            # The buffers' width, not self.dim: a table stores its rows
            # padded to the lane tile (MatrixTable.stored_cols).  The
            # padding is zero, adds nothing to a dot product, gets a zero
            # gradient and stays zero.
            D = dout.shape[1]
            with jax.named_scope("tables.gather"):
                vc = din[c]
                uo = dout[o]
                un = dout[neg.reshape(-1)].reshape(B, K, D)
            with jax.named_scope("sgns.grad"):
                loss, grads = jax.value_and_grad(
                    _sgns_loss, argnums=(0, 1, 2))(vc, uo, un)
            dvc, duo, dun = grads
            din, sin = scatter_apply(upd_in, din, sin, c, dvc, opt)
            out_rows = jnp.concatenate([o, neg.reshape(-1)])
            out_delta = jnp.concatenate([duo, dun.reshape(B * K, D)])
            dout, sout = scatter_apply(upd_out, dout, sout, out_rows,
                                       out_delta, opt)
            return din, sin, dout, sout, loss

        self._fused_cache[batch_axis] = (step, place)
        return step, place

    def train_epoch_fused(self, corpus: np.ndarray, batch_size: int,
                          seed: int = 0) -> Tuple[int, float]:
        from ..util import prefetch_to_device

        with tracing.span("mv.sgns.epoch"):
            step, place = self.make_fused_step()
            din, sin = self.table_in.raw_value()
            dout, sout = self.table_out.raw_value()
            loss = jnp.zeros(())
            steps = 0
            # Index batches go device-side one step ahead of the compiled
            # step (H2D rides behind the previous step's compute), placed
            # by the same batch_placer closure the step's shardings expect.
            for c, o, neg in prefetch_to_device(
                    self.batches(corpus, batch_size, seed=seed), size=2,
                    sharding=place):
                with tracing.span("mv.sgns.dispatch", step=steps):
                    din, sin, dout, sout, loss = step(
                        din, sin, dout, sout, c, o, neg)
                steps += 1
            if steps == 0:
                raise ValueError(
                    f"corpus of {corpus.shape[0]} tokens produced no full "
                    f"batch of {batch_size} pairs (partial batches are "
                    "dropped for static shapes)")
            with tracing.span("mv.sgns.sync"):
                self.table_in.raw_assign(din, sin)
                self.table_out.raw_assign(dout, sout)
                loss = float(loss)
        return steps, loss

    # ------------------------------------------------------------- analysis
    def most_similar(self, token: int, topk: int = 5) -> np.ndarray:
        emb = self.table_in.get()
        v = emb[token] / (np.linalg.norm(emb[token]) + 1e-8)
        norms = emb / (np.linalg.norm(emb, axis=1, keepdims=True) + 1e-8)
        sims = norms @ v
        sims[token] = -np.inf
        return np.argsort(-sims)[:topk]
