"""Distributed multi-sense word embedding (skip-gram mixture).

Reference (SURVEY.md §2.36, ``Microsoft/distributed_skipgram_mixture``
linking libmultiverso): each word owns S sense vectors plus a sense-prior
vector, all parameter-server-resident; workers pull the rows a batch
touches, run an EM step — E: posterior responsibility of each sense given
the occurrence's WHOLE context window (per-pair posteriors are too weak to
break sense symmetry); M: responsibility-weighted SGNS gradients and prior
counts — and push row deltas back.

TPU-native: three row-sharded tables —

- ``table_sense`` [V·S, D]: sense (input) vectors; word w's senses live in
  rows ``w·S … w·S+S-1`` (contiguous, so one word's senses land on one
  shard the way the reference keeps them on one server);
- ``table_out`` [V, D]: context (output) vectors, single-sense as in the
  reference;
- ``table_prior`` [V, S]: Dirichlet-style responsibility counts (plain-add
  updater — counts accumulate, they are not gradients).

Batches are whole occurrences: center [B], context bag [B, C] + validity
mask (static C = 2·window, padded), negatives [B, K].  The fused step
compiles the pull → E-step → weighted-grad → push round trip into one XLA
program: gathers and scatter-applies cross shards over ICI,
responsibilities run in float32 under ``stop_gradient`` (the E-step is
not differentiated through — exactly EM).
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import context as core_context
from ..tables import MatrixTable
from ..updaters import AddOption

__all__ = ["SkipGramMixture", "synthetic_homonym_corpus"]


def synthetic_homonym_corpus(num_tokens: int, vocab_size: int,
                             homonym: int = 0, groups=((1, 10), (11, 20)),
                             seed: int = 0) -> np.ndarray:
    """Token stream where ``homonym`` appears in two disjoint context
    worlds (group-A neighbours vs group-B neighbours) — the canonical
    two-sense test corpus.  Other tokens are drawn uniformly inside their
    own group, so each has one sense."""

    hi_max = max(hi for _, hi in groups)
    if hi_max >= vocab_size:
        raise ValueError(
            f"group token {hi_max} >= vocab_size {vocab_size}; wrapping "
            "would alias group tokens onto other ids (even the homonym)")
    rng = np.random.RandomState(seed)
    out = np.empty(num_tokens, np.int64)
    i = 0
    while i < num_tokens:
        lo, hi = groups[rng.randint(len(groups))]
        run = min(rng.randint(4, 9), num_tokens - i)
        seg = rng.randint(lo, hi + 1, size=run)
        seg[rng.randint(run)] = homonym       # plant the homonym mid-run
        out[i:i + run] = seg
        i += run
    return out.astype(np.int32)


def _mixture_stats(vs, uc, un, mask, log_prior):
    """E-step over a context bag.

    ``vs`` [B,S,D] sense vectors, ``uc`` [B,C,D] context bag, ``un``
    [B,K,D] negatives, ``mask`` [B,C] validity.  Returns (resp [B,S] f32
    stop-gradiented, loglik [B,S] f32).  Float32 throughout — posterior
    odds underflow in bf16.
    """
    pos = jnp.einsum("bsd,bcd->bsc", vs, uc).astype(jnp.float32)
    neg = jnp.einsum("bsd,bkd->bsk", vs, un).astype(jnp.float32)
    loglik = (jnp.sum(jax.nn.log_sigmoid(pos)
                      * mask.astype(jnp.float32)[:, None, :], axis=-1)
              + jnp.sum(jax.nn.log_sigmoid(-neg), axis=-1))
    resp = jax.nn.softmax(loglik + log_prior, axis=-1)
    return jax.lax.stop_gradient(resp), loglik


def _weighted_sgns_loss(vs, uc, un, mask, resp):
    """M-step objective: responsibility-weighted SGNS loss (mean/batch)."""
    _, loglik = _mixture_stats(vs, uc, un, mask, jnp.zeros(resp.shape))
    return -jnp.sum(resp * loglik) / vs.shape[0]


class SkipGramMixture:
    """Multi-sense word2vec over sense/context/prior MatrixTables."""

    def __init__(self, vocab_size: int, dim: int, senses: int = 2,
                 learning_rate: float = 0.05,
                 negatives: int = 5,
                 window: int = 5,
                 updater_type: str = "sgd",
                 name: str = "sgmix",
                 seed: int = 0):
        self.vocab_size = int(vocab_size)
        self.dim = int(dim)
        self.senses = int(senses)
        self.negatives = int(negatives)
        self.window = int(window)
        self.option = AddOption(learning_rate=learning_rate)
        rng = np.random.RandomState(seed)
        # Senses must start apart — identical init keeps responsibilities
        # symmetric forever (EM's classic degenerate fixed point).
        init_sense = (rng.randn(vocab_size * senses, dim)
                      / np.sqrt(dim)).astype(np.float32)
        self.table_sense = MatrixTable(vocab_size * senses, dim,
                                       init=init_sense,
                                       updater_type=updater_type,
                                       name=f"{name}_sense",
                                       default_option=self.option)
        # Output vectors start random too (word2vec's zero init is a
        # symmetric EM fixed point here: zero scores → uniform posteriors
        # → identical sense gradients, forever).
        init_out = (rng.randn(vocab_size, dim)
                    / np.sqrt(dim)).astype(np.float32)
        self.table_out = MatrixTable(vocab_size, dim, init=init_out,
                                     updater_type=updater_type,
                                     name=f"{name}_out",
                                     default_option=self.option)
        # Dirichlet(1) prior counts; plain add (counts, not gradients).
        self.table_prior = MatrixTable(vocab_size, senses,
                                       init=np.ones((vocab_size, senses),
                                                    np.float32),
                                       updater_type="default",
                                       name=f"{name}_prior")
        self._fused_cache = {}

    # ------------------------------------------------------------- batching
    @property
    def bag_width(self) -> int:
        return 2 * self.window

    def batches(self, corpus: np.ndarray, batch_size: int, seed: int = 0):
        """Whole-occurrence examples, static shapes: center [B], context
        bag [B, C] (C = 2·window), mask [B, C], negatives [B, K].

        Padding slots carry ``vocab_size`` — past the visible rows, so
        their (zero-masked) scatter lands in the table's invisible padded
        region instead of touching word 0's state under a non-linear
        updater."""
        rng = np.random.RandomState(seed)
        n = corpus.shape[0]
        C = self.bag_width
        cs, bags, masks = [], [], []
        for i in range(n):
            w = 1 + rng.randint(self.window)
            ctx = np.concatenate([corpus[max(0, i - w):i],
                                  corpus[i + 1:min(n, i + w + 1)]])
            bag = np.full(C, self.vocab_size, np.int32)
            m = np.zeros(C, bool)
            bag[:ctx.shape[0]] = ctx
            m[:ctx.shape[0]] = True
            cs.append(corpus[i]); bags.append(bag); masks.append(m)
            if len(cs) == batch_size:
                neg = rng.randint(self.vocab_size,
                                  size=(batch_size, self.negatives)
                                  ).astype(np.int32)
                yield (np.asarray(cs, np.int32), np.stack(bags),
                       np.stack(masks), neg)
                cs, bags, masks = [], [], []

    def _sense_rows(self, centers: np.ndarray) -> np.ndarray:
        """[B] word ids → [B·S] sense-row ids (w·S + s)."""
        return (centers.astype(np.int64)[:, None] * self.senses
                + np.arange(self.senses)).reshape(-1)

    # ------------------------------------------------ parity push-pull path
    def train_batch(self, centers: np.ndarray, bags: np.ndarray,
                    mask: np.ndarray, negatives: np.ndarray) -> None:
        """Reference loop body: Get rows → EM step → Add row deltas."""
        B, K = negatives.shape
        C = bags.shape[1]
        S, D = self.senses, self.dim
        sense_rows = self._sense_rows(centers)
        vs = jnp.asarray(self.table_sense.get_rows(sense_rows)
                         ).reshape(B, S, D)
        out_rows = np.concatenate([bags.reshape(-1), negatives.reshape(-1)])
        out_emb = self.table_out.get_rows(out_rows)
        uc = jnp.asarray(out_emb[:B * C]).reshape(B, C, D)
        un = jnp.asarray(out_emb[B * C:]).reshape(B, K, D)
        prior = jnp.asarray(self.table_prior.get_rows(centers))
        mask_j = jnp.asarray(mask)

        log_prior = jnp.log(prior / jnp.sum(prior, -1, keepdims=True))
        resp, _ = _mixture_stats(vs, uc, un, mask_j, log_prior)
        dvs, duc, dun = jax.grad(_weighted_sgns_loss, argnums=(0, 1, 2))(
            vs, uc, un, mask_j, resp)

        self.table_sense.add_rows(sense_rows,
                                  np.asarray(dvs).reshape(B * S, D),
                                  option=self.option)
        self.table_out.add_rows(
            out_rows,
            np.concatenate([np.asarray(duc).reshape(B * C, D),
                            np.asarray(dun).reshape(B * K, D)]),
            option=self.option)
        self.table_prior.add_rows(centers, np.asarray(resp))

    # ------------------------------------------------------ fused SPMD path
    def make_fused_step(self, batch_axis: str = "worker"):
        """One XLA program: gathers, E-step, weighted grads, scatter-apply.

        Returns ``step(ds, ss, do, so, dp, sp_, c, bags, mask, neg) ->
        (ds, ss, do, so, dp, sp_, loss)`` over (sense, out, prior) table
        raw values, and the index placer."""
        cached = self._fused_cache.get(batch_axis)
        if cached is not None:
            return cached
        ctx = core_context.get_context()
        from ..parallel.sharding import batch_placer
        _, place = batch_placer(ctx.mesh, batch_axis, dtype=jnp.int32)
        from ..updaters.base import scatter_apply

        upd_sense = self.table_sense.updater
        upd_out = self.table_out.updater
        upd_prior = self.table_prior.updater
        opt = self.option
        S = self.senses

        @partial(jax.jit, donate_argnums=(0, 1, 2, 3, 4, 5))
        def step(ds, ss, do, so, dp, sp_, c, bags, mask, neg):
            B, K = neg.shape
            C = bags.shape[1]
            # The buffers' width (MatrixTable.stored_cols): zero padding
            # past self.dim, which every term below leaves zero.
            D = do.shape[1]
            sense_rows = (c[:, None] * S + jnp.arange(S)).reshape(-1)
            vs = ds[sense_rows].reshape(B, S, D)
            uc = do[bags.reshape(-1)].reshape(B, C, D)
            un = do[neg.reshape(-1)].reshape(B, K, D)
            prior = dp[c]
            log_prior = jnp.log(prior / jnp.sum(prior, -1, keepdims=True))
            resp, _ = _mixture_stats(vs, uc, un, mask, log_prior)
            loss, grads = jax.value_and_grad(
                _weighted_sgns_loss, argnums=(0, 1, 2))(vs, uc, un, mask,
                                                        resp)
            dvs, duc, dun = grads
            ds, ss = scatter_apply(upd_sense, ds, ss, sense_rows,
                                   dvs.reshape(B * S, D), opt)
            out_rows = jnp.concatenate([bags.reshape(-1), neg.reshape(-1)])
            out_delta = jnp.concatenate([duc.reshape(B * C, D),
                                         dun.reshape(B * K, D)])
            do, so = scatter_apply(upd_out, do, so, out_rows, out_delta,
                                   opt)
            dp, sp_ = scatter_apply(upd_prior, dp, sp_, c, resp,
                                    self.table_prior.default_option)
            return ds, ss, do, so, dp, sp_, loss

        self._fused_cache[batch_axis] = (step, place)
        return step, place

    def train_epoch_fused(self, corpus: np.ndarray, batch_size: int,
                          seed: int = 0) -> Tuple[int, float]:
        step, place = self.make_fused_step()
        ds, ss = self.table_sense.raw_value()
        do, so = self.table_out.raw_value()
        dp, sp_ = self.table_prior.raw_value()
        loss = jnp.zeros(())
        steps = 0
        for c, bags, mask, neg in self.batches(corpus, batch_size,
                                               seed=seed):
            ds, ss, do, so, dp, sp_, loss = step(
                ds, ss, do, so, dp, sp_, place(c), place(bags),
                place(mask.astype(np.int32)).astype(bool), place(neg))
            steps += 1
        if steps == 0:
            raise ValueError(
                f"corpus of {corpus.shape[0]} tokens produced no full "
                f"batch of {batch_size} occurrences")
        self.table_sense.raw_assign(ds, ss)
        self.table_out.raw_assign(do, so)
        self.table_prior.raw_assign(dp, sp_)
        return steps, float(loss)

    # ------------------------------------------------------------- analysis
    def sense_priors(self, word: int) -> np.ndarray:
        """Normalized sense probabilities for ``word``."""
        counts = self.table_prior.get_rows(np.asarray([word]))[0]
        return counts / counts.sum()

    def sense_posterior(self, word: int, context: np.ndarray) -> np.ndarray:
        """P(sense | word, bag-of-context) — the E-step for one example."""
        context = np.asarray(context, np.int64)
        vs = self.table_sense.get_rows(self._sense_rows(
            np.asarray([word])))                       # [S, D]
        uc = self.table_out.get_rows(context)          # [C, D]
        nll = np.log1p(np.exp(-(vs @ uc.T))).sum(axis=1)  # -Σ log σ(s·c)
        logp = np.log(self.sense_priors(word) + 1e-12) - nll
        logp -= logp.max()
        p = np.exp(logp)
        return p / p.sum()

    def sense_vector(self, word: int, sense: int) -> np.ndarray:
        return self.table_sense.get_rows(
            np.asarray([word * self.senses + sense]))[0]
