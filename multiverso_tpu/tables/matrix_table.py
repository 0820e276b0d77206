"""MatrixTable — 2-D row-sharded parameter matrix.

Reference (SURVEY.md §2.12, ``table/matrix_table.h``): row-partitioned over
server processes; workers Get/Add the whole matrix or a set of row ids — the
sparse-access workhorse behind word2vec and LightLDA.

TPU-native: one ``jax.Array`` [rows, cols] sharded on dim 0 over the table
mesh.  ``get_rows`` compiles to a gather (XLA inserts the all-to-all /
collective-permute needed to fetch off-shard rows over ICI); ``add_rows``
compiles to scatter-apply with the updater fused in.  Row batches are
padded to power-of-two buckets so shapes stay static for the compiler
(SURVEY.md §7 hard-parts: "sparse tables on TPU ... padding/bucketing").
Duplicate rows in a batch are pre-aggregated host-side (segment-sum) so
stateful updaters see one delta per row.

The device buffer is padded twice: in rows to the mesh (``_padded_rows``)
and, from ``LANES`` columns up, in columns to the lane tile
(``stored_cols``; docs/embedding.md "Resting layout"), so that a row is
contiguous on the device.  The eager API speaks ``num_cols``; a fused step
sees the buffer (``raw_value()``), whose trailing columns are zero and stay
zero.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import dashboard
from ..parallel.sharding import shard_along, table_mesh
from ..updaters import AddOption
from .base import (Table, bucket_size as _bucket, host_fetch, host_put,
                   multihost_allgather_list)

__all__ = ["MatrixTable"]

# The TPU tiles an array's two minor dimensions in (8, LANES).  A row whose
# width is not a multiple of LANES pads to one row-major, so for such a
# shape the backend's default layout is the compact transposed one, and a
# row gather or scatter then copies the whole table in and out of every
# program.  Stored at the padded width, the default layout is row-major:
# the same bytes a row-major row takes on the device anyway.  Under twice
# the bytes from LANES columns up (28% at 300); a narrower table would pay
# LANES / num_cols times and keeps its width (its copies are as small as it
# is).
LANES = 128


class MatrixTable(Table):
    kind = "matrix"

    def __init__(self, num_rows: int, num_cols: int, dtype: Any = jnp.float32,
                 init: Optional[np.ndarray] = None, **kw):
        super().__init__(**kw)
        self.num_rows = int(num_rows)
        self.num_cols = int(num_cols)
        self.dtype = jnp.dtype(dtype)
        self._mesh = table_mesh(self._ctx.mesh)
        n = self._mesh.devices.size
        self._padded_rows = ((self.num_rows + n - 1) // n) * n
        self._sharding = shard_along(self._mesh, ndim=2, dim=0)
        self._stored_cols = (((self.num_cols + LANES - 1) // LANES) * LANES
                             if self.num_cols >= LANES else self.num_cols)

        stored = (self._padded_rows, self._stored_cols)
        # A start-up section (docs/observability.md, "Start-up"): the host
        # buffer at the stored width, then its transfer and the slots'
        # enqueued.  It does not wait for them: a second table's transfer
        # runs beside the first's (on a v5e two tables of 3,000,000 x 384
        # were ready in 63 s for 71 s with a wait here; PERF.md, PR 34).
        with dashboard.monitor("MatrixTable::init_place",
                               rows=self._padded_rows,
                               stored_cols=self._stored_cols):
            host = np.zeros(stored, dtype=self.dtype)
            if init is not None:
                host[: self.num_rows, : self.num_cols] = np.asarray(
                    init, dtype=self.dtype)
            self._data = host_put(host, self._sharding)
            self._state = tuple(
                host_put(np.zeros(stored, dtype=self.dtype), self._sharding)
                for _ in range(self.updater.num_slots))
        # BSP buffers, bucketed per AddOption so a flush applies each
        # option's aggregate with the right hyper-parameters.
        self._pending_dense: Dict[Optional[AddOption], np.ndarray] = {}
        self._pending_sparse: List[
            Tuple[np.ndarray, np.ndarray, Optional[AddOption]]] = []
        # Options whose buffered dense delta is a BORROWED caller array
        # (docs/host_bridge.md): never += into the caller's memory.
        self._pending_borrowed: set = set()
        # Jitted-apply memo keyed per AddOption — bounded by call-site
        # diversity, not data (see base._dense_cache).
        self._rows_cache: Dict[AddOption, Any] = {}  # mvlint: MV007-exempt(jitted-apply memo bounded by call-site diversity)
        # jax.jit caches per input shape internally; one gather fn suffices.
        cols = self.num_cols
        self._gather_fn = jax.jit(lambda data, r: data[r][:, :cols])

    # ------------------------------------------------------------------ Get
    def get(self, option=None, device: bool = False, out=None):
        """Whole-matrix pull (reference ``MatrixWorkerTable::Get`` all-rows).

        ``device=True`` returns a fresh device ``jax.Array`` (no wire hop);
        ``out=`` fills a preallocated host buffer (docs/host_bridge.md)."""
        with self._monitor("Get"):
            if device:
                if out is not None:
                    raise ValueError("out= is a host-path argument")
                return self._slice_device((self.num_rows, self.num_cols))
            # Serve layer: cached + coalesced whole-matrix host read
            # (collective-safe — the key is identical on every rank).
            return self._fill_out(out, self._serve_read(
                ("get",),
                lambda: np.ascontiguousarray(self._locked_read(
                    lambda d, s: host_fetch(d))[: self.num_rows,
                                                : self.num_cols])))

    def get_rows(self, row_ids, option=None, out=None) -> np.ndarray:
        """Row-subset pull — the sparse hot read path.

        Reference: ``MatrixWorkerTable::Get(row_ids)`` partitions ids across
        servers; here it is one compiled gather over the sharded array.

        Multi-host: ranks may ask for different (or no) rows, but the
        gather + fetch are collectives over the non-fully-addressable
        array — so the ids are first unioned across processes and every
        rank runs the identical gather, then slices out its own rows.
        """
        from .base import is_multiprocess

        with self._monitor("GetRows"):
            rows = np.asarray(row_ids, dtype=np.int64)

            # Row-granular serve cache first (docs/embedding.md): each
            # requested row is its own versioned entry, so a hot row
            # keeps hitting across DIFFERENT id sets and a miss fetches
            # only the missing rows — never the whole set.  Disarmed
            # (cache off / -serve_row_cache=false / multi-host) this
            # returns None and the id-set path below takes over.
            if rows.shape[0]:
                def fetch_subset(sub):
                    got = self._gather_host(
                        np.asarray(sub, np.int64).astype(np.int32))
                    return list(got)

                vals = self._serve_read_rows(
                    "row", [int(r) for r in rows], fetch_subset,
                    note_keys=rows.tolist())
                if vals is not None:
                    # np.stack allocates the caller's fresh result — the
                    # cached (read-only) rows are never handed out
                    # mutably.
                    return self._fill_out(
                        out, np.stack(vals).astype(self.dtype,
                                                   copy=False))

            def fetch():
                if is_multiprocess():
                    union = self._allgather_row_ids(rows)
                    k = union.shape[0]
                    if k == 0:
                        return np.zeros((0, self.num_cols),
                                        dtype=self.dtype)
                    fetched = self._gather_host(union.astype(np.int32))
                    if rows.shape[0] == 0:
                        return np.zeros((0, self.num_cols),
                                        dtype=self.dtype)
                    return fetched[np.searchsorted(union, rows)]
                if rows.shape[0] == 0:
                    return np.zeros((0, self.num_cols), dtype=self.dtype)
                return self._gather_host(rows.astype(np.int32))

            # Serve layer: per-id-set cache entries, gated by the max
            # version over the TOUCHED row buckets (adds to other rows
            # keep these hitting).  collective_safe=False — ranks may
            # request different ids, and a rank-local hit would break
            # the union collective, so multi-host bypasses the cache.
            return self._fill_out(out, self._serve_read(
                ("rows", tuple(rows.tolist())), fetch,
                buckets=rows, collective_safe=False,
                keys=rows.tolist()))

    def _gather_host(self, rows: np.ndarray) -> np.ndarray:
        """Bucketed compiled gather + host fetch of ``rows`` (all ranks
        must call with identical ids under multi-host)."""
        k = rows.shape[0]
        b = _bucket(k)
        padded = np.zeros(b, dtype=np.int32)
        padded[:k] = rows
        out = self._locked_read(
            lambda d, s: self._gather_fn(d, jnp.asarray(padded)))
        return host_fetch(out)[:k]

    @staticmethod
    def _allgather_row_ids(rows: np.ndarray) -> np.ndarray:
        """Sorted union of every rank's requested row ids (collective)."""
        parts = multihost_allgather_list(rows)
        return np.unique(np.concatenate(parts))

    # ------------------------------------------------------------------ Add
    def add(self, delta, option: Optional[AddOption] = None,
            sync: bool = False, compress: Optional[str] = None,
            borrow: bool = False) -> None:
        """Whole-matrix add (reference ``Add`` all-rows path).

        ``compress="1bit"``: sign-bit wire format with error feedback
        (see ``ArrayTable.add``).  ``borrow=True``: skip the defensive
        astype/copy — the caller guarantees dtype/layout and no
        mutation until applied (docs/host_bridge.md)."""
        with self._monitor("Add"):
            if compress is None and self._try_device_add(
                    delta, (self.num_rows, self.num_cols), option, sync):
                return
            if compress is None:
                # -wire_codec=1bit: host dense adds default to the 1-bit
                # wire format (docs/wire_compression.md).
                compress = self._wire_compress_default()
            delta = self._coerce_delta(delta, borrow)
            if delta.shape != (self.num_rows, self.num_cols):
                raise ValueError(
                    f"delta shape {delta.shape} != "
                    f"({self.num_rows}, {self.num_cols})")
            if compress is not None:
                self._add_compressed(delta, option, compress, sync)
                return
            if self.sync:
                with self._lock:
                    if option in self._pending_dense:
                        if option in self._pending_borrowed:
                            self._pending_dense[option] = (
                                self._pending_dense[option] + delta)
                            self._pending_borrowed.discard(option)
                        else:
                            self._pending_dense[option] += delta
                    elif borrow:
                        # Buffer the caller's array itself; a second add
                        # to this option allocates a fresh sum above.
                        self._pending_dense[option] = delta
                        self._pending_borrowed.add(option)
                    else:
                        self._pending_dense[option] = delta.astype(
                            self.dtype, copy=True)
                return
            self._apply_dense_now(delta, option)
            if sync:
                jax.block_until_ready(self._data)

    def add_rows(self, row_ids, delta, option: Optional[AddOption] = None,
                 sync: bool = False, borrow: bool = False) -> None:
        """Row-subset push — the sparse hot write path (§3.3 with rows).

        ``borrow=True`` skips the defensive delta copy/convert; the BSP
        buffer then holds the caller's array until the barrier flush."""
        with self._monitor("AddRows"):
            rows = np.asarray(row_ids, dtype=np.int64)
            delta = self._coerce_delta(delta, borrow)
            if delta.shape != (rows.shape[0], self.num_cols):
                raise ValueError("rows/delta shape mismatch")
            if self.sync:
                with self._lock:
                    self._pending_sparse.append((rows, delta, option))
                return
            self._apply_rows_now(rows, delta, option)
            if sync:
                jax.block_until_ready(self._data)

    def flush(self) -> None:
        with self._lock:
            dense, self._pending_dense = self._pending_dense, {}
            sparse, self._pending_sparse = self._pending_sparse, []
            self._pending_borrowed = set()

        def apply(dense=dense, sparse=sparse):
            by_opt: Dict[Optional[AddOption],
                         List[Tuple[np.ndarray, np.ndarray]]] = {}
            for rows, deltas, option in sparse:
                by_opt.setdefault(option, []).append((rows, deltas))
            for option, batches in by_opt.items():
                rows = np.concatenate([r for r, _ in batches])
                deltas = np.concatenate([d for _, d in batches])
                self._apply_rows_now(rows, deltas, option)
            for option, delta in dense.items():
                self._apply_dense_now(delta, option)

        self._ssp_defer(apply if (dense or sparse) else None)

    def discard_pending(self) -> None:
        with self._lock:
            self._pending_dense = {}
            self._pending_sparse = []
            self._pending_borrowed = set()
            self._stale_queue = []

    # ----------------------------------------------------------- internals
    def _multihost_union(self, uniq: np.ndarray, agg: np.ndarray):
        """Union per-process (rows, deltas) across hosts (collective).

        Multi-host SPMD mapping of per-worker sparse Adds: each process
        contributes its row batch, every process applies the identical
        union batch (duplicates re-aggregated), keeping the global array
        consistent.  Rows and deltas ride one float64 buffer through the
        shared padded-allgather (f64 holds row ids exactly to 2^53).
        """
        from .base import is_multiprocess

        if not is_multiprocess():
            return uniq, agg

        packed = np.empty((uniq.shape[0], self.num_cols + 1),
                          dtype=np.float64)
        packed[:, 0] = uniq
        packed[:, 1:] = agg
        all_packed = np.concatenate(multihost_allgather_list(packed))
        uniq2, inv2 = np.unique(
            all_packed[:, 0].astype(np.int64), return_inverse=True)
        agg2 = np.zeros((uniq2.shape[0], self.num_cols), dtype=self.dtype)
        np.add.at(agg2, inv2, all_packed[:, 1:].astype(self.dtype))
        return uniq2, agg2

    def _apply_dense_now(self, delta: np.ndarray,
                         option: Optional[AddOption]) -> None:
        self._apply_dense_padded(delta, option)

    def _apply_rows_now(self, rows: np.ndarray, delta: np.ndarray,
                        option: Optional[AddOption]) -> None:
        opt = option or self.default_option
        # Pre-aggregate duplicates (segment-sum) so stateful updaters see a
        # single delta per row; reference servers get the same effect from
        # sequential Add application.
        uniq, inv = np.unique(rows, return_inverse=True)
        agg = np.zeros((uniq.shape[0], self.num_cols), dtype=self.dtype)
        np.add.at(agg, inv, delta)
        uniq, agg = self._multihost_union(uniq, agg)

        k = uniq.shape[0]
        b = _bucket(k)
        fn = self._rows_cache.get(opt)
        if fn is None:
            updater = self.updater

            pad = self._stored_cols - self.num_cols

            def _apply(data, state, r, d):
                # The deltas ship at num_cols; the row padding is made here.
                d = jnp.pad(d, ((0, 0), (0, pad))) if pad else d
                return updater.apply_rows(data, state, r, d, opt)

            fn = jax.jit(_apply, donate_argnums=(0, 1))
            self._rows_cache[opt] = fn
        # Padding entries point past the padded row count → scatter drops.
        prows = np.full(b, self._padded_rows, dtype=np.int32)
        prows[:k] = uniq
        pdelta = np.zeros((b, self.num_cols), dtype=self.dtype)
        pdelta[:k] = agg
        with self._lock:
            self._data, self._state = fn(
                self._data, self._state, jnp.asarray(prows),
                jnp.asarray(pdelta))
        # Serve layer: bucket-granular bump — uniq is already the
        # cross-rank union, so every rank stamps identical buckets (and
        # the workload tracker charges the touched rows).
        self._serve_bump(uniq, keys=[int(r) for r in uniq])

    # ------------------------------------------------- fused (in-jit) path
    def raw_value(self) -> Tuple[jax.Array, Tuple[jax.Array, ...]]:
        return self._data, self._state

    def raw_assign(self, data: jax.Array,
                   state: Optional[Tuple[jax.Array, ...]] = None) -> None:
        self._data = data
        if state is not None:
            self._state = state

    @property
    def sharding(self):
        return self._sharding

    @property
    def stored_cols(self) -> int:
        """Columns of the device buffers ``raw_value()`` hands out:
        ``num_cols`` rounded up to the lane tile from ``LANES`` columns up.
        The columns past ``num_cols`` hold zeros."""
        return self._stored_cols

    # ------------------------------------------------------------ checkpoint
    def store_state(self) -> Any:
        data, state = self._dense_snapshot((self.num_rows, self.num_cols))
        return {
            "kind": self.kind,
            "shape": (self.num_rows, self.num_cols),
            "data": data,
            "state": state,
        }

    def load_state(self, snap: Any) -> None:
        assert snap["kind"] == self.kind
        assert tuple(snap["shape"]) == (self.num_rows, self.num_cols)
        self._dense_restore(snap["data"], snap["state"],
                            (self.num_rows, self.num_cols))
