"""LightLDA-style distributed topic model (collapsed Gibbs LDA).

Reference (SURVEY.md §2.36, ``Microsoft/LightLDA`` linking libmultiverso):
the word-topic count matrix lives in a SparseMatrixTable (V x K) and the
topic totals in an ArrayTable (K); workers sweep their document shard,
resample token topics, and push count *deltas* with async ``Add`` (plain
add updater) — the AD-LDA scheme where workers sample against slightly
stale counts and reconcile through the server.

TPU-native: the same AD-LDA math, two execution paths:

- ``sample_pass`` — parity path: pull touched word rows + topic totals,
  resample on host, push sparse count deltas (async Add).
- ``make_fused_pass`` — one XLA program per document batch: gather word
  rows, compute the collapsed-Gibbs posterior for every token *in
  parallel* (blocked/AD-LDA approximation — token updates within a batch
  see start-of-batch counts, exactly like workers see stale server state),
  sample with ``jax.random.categorical``, scatter count deltas back.
  Static shapes via padded [docs, max_len] token matrices.  O(K) work and
  memory per token — fine for K up to a few hundred.
- ``make_mh_pass`` — the actual LightLDA algorithm (WWW'15): factorized
  cycle proposals + Metropolis-Hastings, with per-token cost independent
  of K.  The word proposal q_w(k) ∝ (n_kw+β)/(n_k+Vβ) is drawn by
  inverse-CDF binary search — a row-wise ``cumsum`` build is one fused
  parallel op where the reference's Vose alias construction is inherently
  sequential, and the per-draw cost is O(log K) *element* gathers, the
  TPU-native trade for the alias table's O(1).  The doc proposal
  q_d(k) ∝ (n_kd+α) uses LightLDA's token trick (no table at all).
  Acceptance ratios are O(1) element gathers.  Proposal tables are built
  from sweep-start counts and corrected through the acceptance term,
  exactly the staleness the reference's amortized alias tables have.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import context as core_context
from ..tables import ArrayTable, SparseMatrixTable

__all__ = ["LightLDA", "synthetic_documents"]

PAD = -1  # padding token id in [docs, max_len] matrices


def synthetic_documents(num_docs: int, vocab_size: int, num_topics: int,
                        doc_len: int = 64, seed: int = 0,
                        concentration: float = 0.1
                        ) -> Tuple[np.ndarray, np.ndarray]:
    """Documents with planted topic structure; returns (docs, true_topics).

    Each topic owns a contiguous slice of the vocabulary; each doc mixes
    1-2 topics.  ``docs`` is int32 [num_docs, doc_len] (PAD-free here).
    """
    rng = np.random.RandomState(seed)
    words_per_topic = vocab_size // num_topics
    docs = np.zeros((num_docs, doc_len), np.int32)
    true_topics = rng.randint(num_topics, size=num_docs)
    for d in range(num_docs):
        k = true_topics[d]
        own = rng.rand(doc_len) > concentration
        topic_words = (k * words_per_topic
                       + rng.randint(words_per_topic, size=doc_len))
        noise_words = rng.randint(vocab_size, size=doc_len)
        docs[d] = np.where(own, topic_words, noise_words)
    return docs, true_topics


class LightLDA:
    """AD-LDA over a SparseMatrixTable (word-topic) + ArrayTable (totals)."""

    def __init__(self, vocab_size: int, num_topics: int,
                 alpha: float = 0.1, beta: float = 0.01,
                 name: str = "lda",
                 seed: int = 0):
        self.V = int(vocab_size)
        self.K = int(num_topics)
        self.alpha = float(alpha)
        self.beta = float(beta)
        # Plain-add updater and ASP pinned regardless of runtime defaults:
        # LDA pushes count deltas (not gradients) and the AD-LDA scheme
        # requires async Adds visible to the next sweep.
        self.word_topic = SparseMatrixTable(self.V, self.K,
                                            updater_type="default",
                                            sync=False,
                                            name=f"{name}_word_topic")
        self.topic_sum = ArrayTable(self.K, updater_type="default",
                                    sync=False,
                                    name=f"{name}_topic_sum")
        self._key = jax.random.PRNGKey(seed)
        self._fused_cache = {}

    # ------------------------------------------------------------ init pass
    def initialize_counts(self, docs: np.ndarray,
                          seed: int = 0) -> np.ndarray:
        """Random topic init; returns doc-topic counts [D, K] (worker-local
        state in the reference) and pushes global counts."""
        rng = np.random.RandomState(seed)
        D, L = docs.shape
        z = rng.randint(self.K, size=(D, L)).astype(np.int32)
        z[docs == PAD] = -1
        doc_topic = np.zeros((D, self.K), np.float32)
        wt_delta = np.zeros((self.V, self.K), np.float32)
        ts_delta = np.zeros(self.K, np.float32)
        valid = docs != PAD
        for d in range(D):
            for i in np.nonzero(valid[d])[0]:
                k = z[d, i]
                doc_topic[d, k] += 1
                wt_delta[docs[d, i], k] += 1
                ts_delta[k] += 1
        touched = np.unique(docs[valid])
        self.word_topic.add_rows(touched, wt_delta[touched])
        self.topic_sum.add(ts_delta)
        self._z = z
        return doc_topic

    # ------------------------------------------------ parity push-pull path
    def sample_pass(self, docs: np.ndarray, doc_topic: np.ndarray,
                    seed: int = 0) -> np.ndarray:
        """One AD-LDA sweep via eager Get/Add (the reference worker loop)."""
        rng = np.random.RandomState(seed)
        # The fused drivers may hand back an (immutable) device array;
        # this host loop mutates in place, so take a host copy.
        doc_topic = np.array(doc_topic)
        D, L = docs.shape
        valid = docs != PAD
        touched = np.unique(docs[valid])
        wt = self.word_topic.get_rows(touched).astype(np.float64)
        row_of = {int(w): i for i, w in enumerate(touched)}
        ts = self.topic_sum.get().astype(np.float64)
        wt_delta = np.zeros_like(wt)
        ts_delta = np.zeros(self.K, np.float64)
        z = self._z
        for d in range(D):
            for i in np.nonzero(valid[d])[0]:
                w, old = int(docs[d, i]), int(z[d, i])
                r = row_of[w]
                # decrement
                doc_topic[d, old] -= 1
                wt[r, old] -= 1
                ts[old] -= 1
                wt_delta[r, old] -= 1
                ts_delta[old] -= 1
                # collapsed posterior
                p = ((wt[r] + self.beta) * (doc_topic[d] + self.alpha)
                     / (ts + self.V * self.beta))
                p = np.maximum(p, 0)
                new = rng.choice(self.K, p=p / p.sum())
                # increment
                z[d, i] = new
                doc_topic[d, new] += 1
                wt[r, new] += 1
                ts[new] += 1
                wt_delta[r, new] += 1
                ts_delta[new] += 1
        self.word_topic.add_rows(touched, wt_delta.astype(np.float32))
        self.topic_sum.add(ts_delta.astype(np.float32))
        return doc_topic

    # ------------------------------------------------------ fused SPMD path
    def make_fused_pass(self, max_len: int, batch_axis: str = "worker"):
        """Compile one blocked-Gibbs sweep over a doc batch into XLA.

        All tokens resample in parallel against start-of-batch counts
        (AD-LDA staleness, same approximation the reference's async Add
        makes across workers).  Returns
        ``pass_fn(wt, ts, docs, z, doc_topic, key) ->
        (z', doc_topic', topic_sum_delta)`` wired through
        ``run_fused_pass`` (which rebuilds the sparse word-topic deltas
        host-side from ``z``/``z'``).
        """
        cached = self._fused_cache.get((max_len, batch_axis))
        if cached is not None:
            return cached
        ctx = core_context.get_context()
        from ..parallel.sharding import batch_placer
        _, place_f = batch_placer(ctx.mesh, batch_axis)
        V, K, alpha, beta = self.V, self.K, self.alpha, self.beta

        @jax.jit
        def pass_fn(wt, ts, docs, z, doc_topic, key):
            wt = wt[:, :K]      # the table stores rows padded to the lane tile
            valid = docs != PAD
            w_safe = jnp.where(valid, docs, 0)
            # remove each token's own count (collapsed Gibbs "minus self")
            own = jax.nn.one_hot(z, K, dtype=wt.dtype) * valid[..., None]
            wt_tok = wt[w_safe] - own                       # [D, L, K]
            dt_tok = doc_topic[:, None, :] - own            # [D, L, K]
            ts_tok = ts[None, None, :] - own                # [D, L, K]
            logits = (jnp.log(jnp.maximum(wt_tok + beta, 1e-30))
                      + jnp.log(jnp.maximum(dt_tok + alpha, 1e-30))
                      - jnp.log(jnp.maximum(ts_tok + V * beta, 1e-30)))
            new_z = jax.random.categorical(key, logits, axis=-1)
            new_z = jnp.where(valid, new_z, -1)
            # deltas: -old +new per token; only the [D,K]/[K] reductions
            # leave the device — the [D,L,K] intermediate fuses away.
            old_oh = own
            new_oh = jax.nn.one_hot(new_z, K, dtype=wt.dtype) * valid[..., None]
            delta = new_oh - old_oh
            doc_topic = doc_topic + delta.sum(axis=1)
            ts_delta = delta.sum(axis=(0, 1))
            return new_z, doc_topic, ts_delta

        self._fused_cache[(max_len, batch_axis)] = (pass_fn, place_f)
        return pass_fn, place_f

    # ---------------------------------------------- LightLDA MH SPMD path
    def make_mh_pass(self, max_len: int, mh_steps: int = 4,
                     batch_axis: str = "worker"):
        """Compile one LightLDA Metropolis-Hastings sweep into XLA.

        Reference: the WWW'15 LightLDA sampler (``Microsoft/LightLDA``,
        SURVEY.md §2.36/§6) — alternating word/doc cycle proposals with
        O(1) acceptance.  Per-token cost here is O(mh_steps · log K)
        element gathers + O(1) scatters; nothing materializes a K-sized
        axis per token, so throughput holds at K in the thousands where
        the dense kernel's [D·L·K] tensor is the ceiling.

        Same blocked/AD-LDA staleness as ``make_fused_pass``: every token
        proposes and accepts against sweep-start counts (minus its own
        sweep-start assignment — collapsed Gibbs "minus self"), and the
        word-proposal CDF is built once per sweep from those counts, with
        the MH ratio using that same stale density (so the chain targets
        the exact sweep-start posterior — amortized-table staleness is
        corrected through acceptance, as in the reference).
        """
        from ..tables.base import is_multiprocess

        # Trace-time choice: the dense [V, K] wt_delta scatter only exists
        # where it will be consumed (the single-controller device-add path)
        # — multi-host sweeps use the host sparse rebuild and must not pay
        # a discarded [V, K] scatter per sweep.
        with_wt_delta = not is_multiprocess()
        cache_key = ("mh", max_len, mh_steps, batch_axis, with_wt_delta)
        cached = self._fused_cache.get(cache_key)
        if cached is not None:
            return cached
        ctx = core_context.get_context()
        from ..parallel.sharding import batch_placer
        _, place_f = batch_placer(ctx.mesh, batch_axis)
        V, K, alpha, beta = self.V, self.K, self.alpha, self.beta
        n_bits = max(1, (K - 1).bit_length())

        @jax.jit
        def pass_fn(wt, ts, docs, z, doc_topic, key):
            wt = wt[:, :K]      # the table stores rows padded to the lane tile
            D = docs.shape[0]
            valid = docs != PAD
            w = jnp.where(valid, docs, 0)
            z0 = jnp.where(valid, z, 0)
            d_idx = jnp.broadcast_to(jnp.arange(D)[:, None], docs.shape)
            vf = valid.astype(wt.dtype)

            # Sweep-start word-proposal density + CDF (the "alias tables").
            qw = (wt + beta) / (ts + V * beta)[None, :]          # [V, K]
            cdf = jnp.cumsum(qw, axis=-1)                        # [V, K]
            total = cdf[w, K - 1]                                # [D, L]

            # Minus-self π terms: subtract the token's own sweep-start
            # assignment from every count it reads.
            def pi_num(t):
                self_c = ((t == z0) & valid).astype(wt.dtype)
                n_tw = wt[w, t] - self_c
                n_td = doc_topic[d_idx, t] - self_c
                n_t = ts[t] - self_c
                return ((n_tw + beta) * (n_td + alpha)
                        / (n_t + V * beta))

            # Doc-proposal token trick: j-th valid token of doc d, found
            # through a stable sort that packs valid positions first.
            order = jnp.argsort(jnp.where(valid, 0, 1), axis=1,
                                stable=True)                     # [D, L]
            n_d = valid.sum(axis=1).astype(wt.dtype)             # [D]

            s = z0
            pi_s = pi_num(s)
            for step in range(mh_steps):
                key, k1, k2, k3, k4 = jax.random.split(key, 5)
                if step % 2 == 0:
                    # ---- word proposal: inverse-CDF binary search
                    u = jax.random.uniform(k1, docs.shape,
                                           dtype=wt.dtype) * total
                    lo = jnp.zeros(docs.shape, jnp.int32)
                    hi = jnp.full(docs.shape, K - 1, jnp.int32)
                    for _ in range(n_bits):
                        mid = (lo + hi) // 2
                        below = cdf[w, mid] < u
                        lo = jnp.where(below, mid + 1, lo)
                        hi = jnp.where(below, hi, mid)
                    t = hi
                    q_s, q_t = qw[w, s], qw[w, t]
                else:
                    # ---- doc proposal: token trick, q_d(k) ∝ n_kd + α
                    pick_tok = (jax.random.uniform(k1, docs.shape,
                                                   dtype=wt.dtype)
                                * (n_d[:, None] + K * alpha)) < n_d[:, None]
                    j = jnp.floor(jax.random.uniform(k2, docs.shape,
                                                     dtype=wt.dtype)
                                  * n_d[:, None]).astype(jnp.int32)
                    # Clip to n_d-1 per doc: fp32 rounding can make
                    # uniform*n_d land exactly on n_d, which would read a
                    # PAD slot (z0 forced to 0 — a bias toward topic 0).
                    j = jnp.clip(
                        j, 0,
                        jnp.maximum(n_d.astype(jnp.int32) - 1, 0)[:, None])
                    t_tok = z0[d_idx, order[d_idx, j]]
                    t_unif = jax.random.randint(k3, docs.shape, 0, K)
                    t = jnp.where(pick_tok, t_tok, t_unif)
                    q_s = doc_topic[d_idx, s] + alpha
                    q_t = doc_topic[d_idx, t] + alpha
                pi_t = pi_num(t)
                ratio = (pi_t * q_s) / jnp.maximum(pi_s * q_t, 1e-30)
                accept = (jax.random.uniform(k4, docs.shape,
                                             dtype=wt.dtype) < ratio)
                accept = accept & valid
                s = jnp.where(accept, t, s)
                pi_s = jnp.where(accept, pi_t, pi_s)

            new_z = jnp.where(valid, s, -1)
            # Deltas via flat scatter-add: O(tokens), never [D, L, K].
            d_flat = d_idx.reshape(-1)
            w_flat = w.reshape(-1)
            old_flat = z0.reshape(-1)
            new_flat = s.reshape(-1)
            v_flat = vf.reshape(-1)
            dt_delta = (jnp.zeros((D, K), wt.dtype)
                        .at[d_flat, new_flat].add(v_flat)
                        .at[d_flat, old_flat].add(-v_flat))
            ts_delta = (jnp.zeros((K,), wt.dtype)
                        .at[new_flat].add(v_flat)
                        .at[old_flat].add(-v_flat))
            if not with_wt_delta:
                return new_z, doc_topic + dt_delta, ts_delta
            # Word-topic delta scattered on device: the [V, K] count
            # update then rides the table's device-resident add tier
            # (HBM speed) instead of a host round trip that at large K
            # would cost seconds per sweep on the host wire.
            wt_delta = (jnp.zeros((V, K), wt.dtype)
                        .at[w_flat, new_flat].add(v_flat)
                        .at[w_flat, old_flat].add(-v_flat))
            return new_z, doc_topic + dt_delta, ts_delta, wt_delta

        self._fused_cache[cache_key] = (pass_fn, place_f)
        return pass_fn, place_f

    def run_mh_pass(self, docs: np.ndarray, doc_topic,
                    mh_steps: int = 4) -> "jax.Array | np.ndarray":
        """Drive one LightLDA-MH sweep: gather → MH in-jit → push deltas.

        Single-controller, the returned doc-topic matrix is a *device*
        array (it never ships host-side between sweeps); ``np.asarray``
        it for host analysis.  Accepts either kind as input.
        """
        pass_fn, place = self.make_mh_pass(docs.shape[1], mh_steps)
        return self._drive_pass(pass_fn, place, docs, doc_topic,
                                device_wt_delta=True)

    def run_fused_pass(self, docs: np.ndarray,
                       doc_topic: np.ndarray) -> np.ndarray:
        """Drive one fused sweep: gather → sample in-jit → push deltas."""
        pass_fn, place = self.make_fused_pass(docs.shape[1])
        return self._drive_pass(pass_fn, place, docs, doc_topic)

    def _drive_pass(self, pass_fn, place, docs: np.ndarray, doc_topic,
                    device_wt_delta: bool = False):
        """Shared driver for the fused/MH SPMD sweeps: pull table state,
        run the jitted pass, push sparse deltas back through the tables.

        ``device_wt_delta``: the pass also returns a dense [V, K]
        word-topic delta which (single-controller) goes straight through
        the table's device-resident add — no host round trip, so sweep
        cost stays sampler-bound at large K.  ``doc_topic`` may be (and
        is returned as) a device array so it never ships host-side
        between sweeps either; ``np.asarray`` it for analysis.
        """
        from ..tables.base import is_multiprocess

        # make_mh_pass omits the wt_delta output at trace time under
        # multi-host (the host sparse rebuild runs instead); mirror that.
        device_wt_delta = device_wt_delta and not is_multiprocess()
        self._key, sub = jax.random.split(self._key)
        wt_full, _ = self.word_topic.raw_value()
        ts = jnp.asarray(self.topic_sum.get())
        # Doc-dimension arrays shard over the worker axis (data parallelism);
        # the word-topic table stays on its own shards; XLA lays the gathers
        # and the one-hot reductions across ICI.
        old_z = self._z
        outs = pass_fn(
            wt_full, ts, place(jnp.asarray(docs)),
            place(jnp.asarray(old_z)), place(jnp.asarray(doc_topic)), sub)
        if device_wt_delta:
            new_z, new_dt, ts_delta, wt_delta = outs
        else:
            (new_z, new_dt, ts_delta), wt_delta = outs, None
        self._z = np.asarray(new_z)
        if wt_delta is not None:
            self.word_topic.add(wt_delta)      # device-resident tier
            self.topic_sum.add(ts_delta)       # ditto (jax.Array routes)
            return new_dt
        # Word-topic deltas rebuilt sparsely on host from (old_z, new_z):
        # [touched_words, K] instead of shipping a dense [D, L, K].
        # (Also the multi-host path: eager adds must be the lockstep
        # host collectives, not per-rank device applies.)
        valid = docs != PAD
        w_flat = docs[valid]
        old_flat = old_z[valid]
        new_flat = self._z[valid]
        touched, inv = np.unique(w_flat, return_inverse=True)
        agg = np.zeros((touched.size, self.K), np.float32)
        np.add.at(agg, (inv, old_flat), -1.0)
        np.add.at(agg, (inv, new_flat), 1.0)
        self.word_topic.add_rows(touched, agg)
        self.topic_sum.add(np.asarray(ts_delta))
        return np.asarray(new_dt)

    def close(self) -> None:
        """Release both tables' device memory (see ``Table.close``)."""
        self.word_topic.close()
        self.topic_sum.close()
        self._fused_cache.clear()

    # ------------------------------------------------------------- analysis
    def topic_purity(self, docs: np.ndarray, true_topics: np.ndarray,
                     doc_topic: np.ndarray) -> float:
        """Fraction of docs whose argmax inferred topic maps 1:1 to the
        planted topic (best matching via greedy assignment)."""
        inferred = doc_topic.argmax(axis=1)
        K = self.K
        conf = np.zeros((K, K))
        for inf, true in zip(inferred, true_topics):
            conf[inf, true] += 1
        purity = 0.0
        used = set()
        for inf in np.argsort(-conf.max(axis=1)):
            best = int(np.argmax(
                [conf[inf, t] if t not in used else -1 for t in range(K)]))
            used.add(best)
            purity += conf[inf, best]
        return purity / len(true_topics)
