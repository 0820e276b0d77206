"""Table base class.

Reference (SURVEY.md §2.10, ``table_interface.h``): a table is a
worker-side stub (``WorkerTable::{Get,Add,Partition,Wait,Notify}``) plus
server-side shards (``ServerTable::{ProcessGet,ProcessAdd,Store,Load}``)
connected by request/reply messages.

TPU-native redesign: **the worker/server split disappears into sharded
device memory.** A table owns

- ``_data``  — a ``jax.Array`` sharded over the 1-D table mesh (the "server
  shards"),
- ``_state`` — the updater's state arrays, sharded identically (per-row
  optimizer state lives with its rows),

and two execution paths:

- the *eager parity path* — ``get()``/``add()`` with host arrays, matching
  the reference C-API semantics (used by the bindings and the ported apps);
- the *fused path* — ``raw_value()``/``raw_assign()`` handing the sharded
  arrays to a jitted training step so Get/Add/update fuse into one XLA
  program (the TPU-native hot loop).

Sync (BSP) vs async (ASP) semantic mapping (SURVEY.md §7 hard-parts):
``sync=False`` (ASP default) applies every ``add`` immediately — workers see
each other's updates as soon as XLA commits them.  ``sync=True`` (BSP)
buffers adds for the current clock; ``flush()`` — triggered by
``barrier()``, i.e. the clock boundary — aggregates and applies them in one
updater call, exactly the reference sync-server behavior of holding replies
until all adds for clock *t* arrive.
"""

from __future__ import annotations

import threading
from typing import Any, Optional

from .. import config, dashboard, fault, metrics, tracing
from ..core import context as core_context
from ..updaters import AddOption, get_updater

__all__ = ["Table", "host_fetch", "host_put", "is_multiprocess",
           "bucket_size", "multihost_allgather_list"]


def _upto(extents) -> tuple:
    """The index of an array's leading region: ``[:n]`` along each axis."""
    return tuple(slice(0, n) for n in extents)


def bucket_size(k: int, floor: int = 8) -> int:
    """Round ``k`` up to a power-of-two bucket (shape-stable collectives:
    ``process_allgather`` jits per shape, so bucketing caps recompiles)."""
    b = floor
    while b < k:
        b *= 2
    return b


def is_multiprocess() -> bool:
    """One predicate for every lockstep-collective guard in the tables.

    All multi-host paths (``host_fetch``/``multihost_sum``/the sparse
    union) MUST use this same test — two spellings that ever diverged
    would leave one rank inside a collective the other skipped: deadlock.
    """
    import jax

    return jax.process_count() > 1


def host_fetch(arr):
    """Device->host materialization that also works multi-host.

    Single-controller arrays ``device_get`` directly; a ``jax.Array``
    with shards on other hosts (``process_count() > 1``) is first
    gathered with a cross-host ``process_allgather`` — the reference's
    server->worker Reply_Get hop (SURVEY.md §3.2), here one collective.
    Collective: under multi-host every process must call it together.
    """
    import jax
    import numpy as np

    if isinstance(arr, jax.Array) and not arr.is_fully_addressable:
        from jax.experimental import multihost_utils

        return np.asarray(multihost_utils.process_allgather(arr, tiled=True))
    return np.asarray(jax.device_get(arr))


def multihost_sum(host_delta):
    """Sum per-process host deltas across processes (collective).

    Multi-host SPMD mapping of the reference's many-workers-Add semantics
    (SURVEY.md §3.3): every worker process pushes its own delta, the
    "server" applies the sum.  Under a single controller this is the
    identity; under ``process_count() > 1`` every process MUST call adds
    in lockstep (eager adds become collective), and each then applies the
    identical summed delta, keeping the global jax.Array consistent.
    """
    import numpy as np

    if not is_multiprocess():
        return host_delta
    from jax.experimental import multihost_utils

    return np.asarray(
        multihost_utils.process_allgather(host_delta)).sum(axis=0)


def multihost_allgather_list(arr):
    """Allgather variable-length per-rank arrays; returns one array per rank.

    THE one spelling of the "size probe + pad + gather" collective every
    table-layer multi-host path uses (a second divergent spelling that
    skipped the probe on some rank would deadlock the job).  Two rounds:
    a length probe so ranks agree on one padded gather shape, then the
    payload.  ``arr`` is per-rank [k_r, ...]; the result list holds each
    rank's trimmed contribution in rank order.  Collective: every process
    must call it together (even with ``k_r == 0``).
    """
    import numpy as np

    if not is_multiprocess():
        return [arr]
    from jax.experimental import multihost_utils

    n = arr.shape[0]
    lens = np.asarray(multihost_utils.process_allgather(
        np.array([n], np.int64))).ravel()
    b = bucket_size(max(int(lens.max()), 1))
    padded = np.zeros((b,) + arr.shape[1:], dtype=arr.dtype)
    padded[:n] = arr
    gathered = np.asarray(multihost_utils.process_allgather(padded))
    return [gathered[r, : int(lens[r])] for r in range(lens.shape[0])]


def host_put(host, sharding):
    """Host->device placement that also works multi-host.

    ``device_put`` requires every target device to be addressable; on a
    multi-host mesh each process instead contributes its addressable
    shards of the (replicated) host array via ``make_array_from_callback``.
    """
    import jax

    if sharding.is_fully_addressable:
        return jax.device_put(host, sharding)
    return jax.make_array_from_callback(host.shape, sharding,
                                        lambda idx: host[idx])


class Table:
    """Common lifecycle: registration, updater selection, BSP buffering."""

    kind = "table"

    # Serve-layer version buckets (docs/serving.md): row/key applies
    # stamp only their bucket, so reads of untouched buckets can keep
    # hitting the cache across unrelated adds.  Must match the native
    # plane's ServerTable::kVersionBuckets.
    SERVE_BUCKETS = 64

    def __init__(self, name: Optional[str] = None,
                 updater_type: Optional[str] = None,
                 sync: Optional[bool] = None,
                 default_option: Optional[AddOption] = None,
                 staleness: int = 0,
                 serve_cache: Optional[int] = None,
                 max_staleness: Optional[int] = None):
        ctx = core_context.get_context()
        self._ctx = ctx
        if updater_type is None:
            updater_type = ctx.updater_type
        self.updater = get_updater(updater_type)
        self.updater_type = updater_type
        self.sync = ctx.sync if sync is None else bool(sync)
        self.staleness = int(staleness)
        if self.staleness < 0:
            raise ValueError(f"staleness must be >= 0, got {staleness}")
        if self.staleness and not self.sync:
            raise ValueError(
                "staleness (SSP) requires a sync=True table — ASP has no "
                "clock to be stale against")
        # SSP deferral queue: (clock, apply_fn) flushes waiting out their
        # staleness bound (see _ssp_defer).
        self._stale_queue: list = []
        self.default_option = default_option or AddOption()
        self.table_id = ctx.register_table(self)
        self.name = name or f"{self.kind}_{self.table_id}"
        # Names key checkpoints; a silent duplicate would drop state on save.
        for other in ctx.tables():
            if other is not self and other.name == self.name:
                # Leave no half-constructed table behind: barrier()/shutdown
                # iterate the registry and would touch it.
                ctx.unregister_table(self.table_id)
                raise ValueError(
                    f"duplicate table name '{self.name}' (held by another "
                    f"{other.kind} table); pass a unique name=")
        self._lock = threading.Lock()
        # Jitted-apply memo, NOT a data cache: keyed by (AddOption,
        # shape/path) — bounded by call-site diversity (a handful of
        # compiled fns per table), never by traffic.
        self._dense_cache: dict = {}  # mvlint: MV007-exempt(jitted-apply memo keyed by call-site diversity, not traffic)
        self._compressor = None  # lazy OneBitCompressor (error feedback)
        self._closed = False
        # --- serve layer (docs/serving.md): versioned read cache -----------
        # The "server version" of a JAX-plane table is its local apply
        # counter; eager applies are lockstep collectives under
        # multi-host, so the counter advances IDENTICALLY on every rank
        # and cached whole-table reads stay collective-safe (all ranks
        # hit or all miss together).  Arm via -serve_cache_entries (or
        # the serve_cache= kwarg); max_staleness is a VERSION distance
        # (0 = cached reads never stale), NOT the SSP clock staleness=.
        self._serve_version = 0
        self._serve_buckets = None              # lazily [SERVE_BUCKETS]
        self._serve_ver_lock = threading.Lock()
        # Fleet routing epoch last adopted (docs/replication.md): a
        # promotion/join flip voids the serve cache via note_routing_epoch.
        self._routing_epoch = 0
        self._serve_staleness = int(
            config.get("max_staleness") if max_staleness is None
            else max_staleness)
        if self._serve_staleness < 0:
            raise ValueError(
                f"max_staleness must be >= 0, got {self._serve_staleness}")
        # --- workload plane (docs/observability.md) ---------------------
        # Mirror of the native server's hot-key/load accounting: a
        # space-saving top-K + count-min tracker fed by the eager
        # get/add paths, so the pure-JAX plane reports the same shapes
        # the native "hotkeys" OpsQuery kind serves.
        if bool(config.get("hotkey_enabled")):
            from ..sketch import WorkloadTracker

            self._workload = WorkloadTracker(
                topk=int(config.get("hotkey_topk")),
                buckets=self.SERVE_BUCKETS)
        else:
            self._workload = None
        entries = int(config.get("serve_cache_entries")
                      if serve_cache is None else serve_cache)
        # Row-granular cache arm (docs/embedding.md): per-id reads cache
        # INDIVIDUAL rows/keys instead of whole id-set tuples, so a hot
        # row keeps hitting across different id sets.  Rides the same
        # VersionedLRUCache; -serve_row_cache=false reverts to the PR 4
        # id-set entries.
        self._serve_row_cache = bool(config.get("serve_row_cache"))
        if entries > 0:
            from ..serve import Coalescer, VersionedLRUCache

            self._serve_cache = VersionedLRUCache(entries)
            self._serve_coalescer = Coalescer(
                window_s=float(config.get("coalesce_window_us")) * 1e-6,
                max_batch=int(config.get("serve_max_batch")))
        else:
            self._serve_cache = None
            self._serve_coalescer = None

    def _apply_dense_padded(self, delta, option, *,
                            presummed: bool = False) -> None:
        """Shared eager dense-apply: pad to the sharded shape, ship, update.

        Used by the Array/Matrix ``add`` paths.  The jitted apply donates
        ``_data``/``_state``, so the swap holds ``_lock`` — a concurrent
        eager add reading a donated (deleted) buffer would crash otherwise.
        ``presummed`` marks a delta already merged across ranks (the
        compressed path) — it skips the multi-host sum collective.
        """
        import jax
        import numpy as np

        opt = option or self.default_option
        fn = self._dense_cache.get(opt)
        if fn is None:
            updater = self.updater

            def _apply(data, state, d):
                return updater.apply_dense(data, state, d, opt)

            fn = jax.jit(_apply, donate_argnums=(0, 1))
            self._dense_cache[opt] = fn
        padded_shape = self._data.shape
        if tuple(delta.shape) == tuple(padded_shape):
            # Already padded-size (e.g. the table divides the mesh
            # evenly): skip the zero-fill + copy — at tens of MiB that
            # alloc+memcpy costs a measurable slice of the wire budget.
            padded = np.ascontiguousarray(delta, dtype=self.dtype)
        else:
            padded = np.zeros(padded_shape, dtype=self.dtype)
            padded[_upto(delta.shape)] = delta
        if not presummed:
            padded = multihost_sum(padded)
        d = host_put(padded, self._sharding)
        with self._lock:
            self._data, self._state = fn(self._data, self._state, d)
        self._serve_bump()

    def _wire_compress_default(self):
        """Resolve the ``-wire_codec`` flag into a default ``compress=``
        for host dense adds (docs/wire_compression.md): ``"1bit"`` when
        the flag says so AND this table can carry it (float dtype, not
        BSP — the residual is per wire message), else ``None``.  An
        explicit ``compress=`` kwarg always wins; the device fast path
        and the sparse codec stay native/wire concepts."""
        import jax.numpy as jnp

        if config.get("wire_codec") != "1bit" or self.sync:
            return None
        return "1bit" if jnp.issubdtype(self.dtype, jnp.floating) else None

    def _add_compressed(self, delta, option, compress: str,
                        blocking: bool) -> None:
        """Shared compress= dispatch for the dense table ``add`` paths:
        validation (codec name, BSP incompatibility, float dtype) in ONE
        place, then the 1-bit apply."""
        import jax
        import jax.numpy as jnp

        # Chaos seam (docs/fault_tolerance.md): a scripted encode
        # failure surfaces here, exactly where a real codec error would.
        fault.inject("codec.encode")
        if compress != "1bit":
            raise ValueError(
                f"unknown compress '{compress}' (expected '1bit')")
        if self.sync:
            raise ValueError(
                "compress='1bit' is incompatible with BSP buffering "
                "(the residual is per-wire-message)")
        if not jnp.issubdtype(self.dtype, jnp.floating):
            # Fractional quantization scales would truncate into an int
            # table and the residual could never compensate.
            raise ValueError(
                f"compress='1bit' requires a floating table, got "
                f"{self.dtype}")
        self._apply_dense_compressed(delta, option)
        if blocking:
            jax.block_until_ready(self._data)

    def _apply_dense_compressed(self, delta, option) -> None:
        """1-bit-SGD eager add (SURVEY.md §5 quantization lineage).

        Quantize (with this table's error-feedback residual), move only
        sign bits + two scales over the wire — under multi-host, the
        allgather ships 1/32 the bytes — then every rank dequantizes the
        identical payloads and applies the identical sum.  Lossy per
        add; the residual re-injects the loss into the next add, which
        is what keeps SGD convergent (Seide et al. 2014).
        """
        import numpy as np

        from ..util.quantization import OneBitCompressor, dequantize_1bit

        # Residual read-modify-write under the table lock: concurrent
        # compressed adds racing it would double-inject one residual and
        # drop another — silently wrong values.
        with self._lock:
            if self._compressor is None:
                self._compressor = OneBitCompressor()
            packed, p, m = self._compressor.compress(delta)
        shape = delta.shape
        if is_multiprocess():
            header = np.frombuffer(
                np.asarray([p, m], np.float64).tobytes(), np.uint8)
            parts = multihost_allgather_list(
                np.concatenate([header, packed]))
            total = np.zeros(int(np.prod(shape)), np.float32)
            for part in parts:
                ps, ms = np.frombuffer(part[:16].tobytes(), np.float64)
                total += dequantize_1bit(part[16:], float(ps), float(ms),
                                         total.size)
            self._apply_dense_padded(total.reshape(shape), option,
                                     presummed=True)
            return
        # Single controller: ship the PACKED BITS to the device (1/32 the
        # host->device bytes — the host link is this path's bottleneck)
        # and unpack + scale + apply in one jitted program.
        self._apply_packed_device(packed, p, m, shape, option)

    def _apply_packed_device(self, packed, pos_scale, neg_scale, shape,
                             option) -> None:
        """Jitted 1-bit decode + updater apply (donated table buffers)."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        opt = option or self.default_option
        key = (opt, "packed", tuple(shape))
        fn = self._dense_cache.get(key)
        if fn is None:
            updater = self.updater
            padded_shape = self._data.shape
            n = int(np.prod(shape))

            def _apply(data, state, bits_u8, scales):
                bits = jnp.unpackbits(bits_u8, count=n).astype(bool)
                d = jnp.where(bits, scales[0], scales[1]).reshape(shape)
                if d.shape != padded_shape:
                    d = jnp.pad(d, [(0, ps - s) for ps, s in
                                    zip(padded_shape, d.shape)])
                return updater.apply_dense(data, state,
                                           d.astype(data.dtype), opt)

            fn = jax.jit(_apply, donate_argnums=(0, 1))
            self._dense_cache[key] = fn
        scales = np.asarray([pos_scale, neg_scale], np.float32)
        with self._lock:
            self._data, self._state = fn(self._data, self._state,
                                         packed, scales)
        self._serve_bump()

    def _apply_dense_device(self, delta, option) -> None:
        """Device-resident eager add: the delta is already a ``jax.Array``.

        No host padding, no host→device ship — one jitted pad+cast+apply
        with donated table buffers, so Add runs at HBM speed (the reference
        server's ProcessAdd with the network hop removed; SURVEY.md §3.3).
        Single-controller only: multi-host adds need the cross-process sum
        and take the host path.
        """
        import jax
        import jax.numpy as jnp

        opt = option or self.default_option
        key = (opt, "device")
        fn = self._dense_cache.get(key)
        if fn is None:
            updater = self.updater
            padded_shape = self._data.shape

            def _apply(data, state, d):
                if d.shape != padded_shape:
                    d = jnp.pad(d, [(0, p - s) for p, s in
                                    zip(padded_shape, d.shape)])
                return updater.apply_dense(data, state,
                                           d.astype(data.dtype), opt)

            fn = jax.jit(_apply, donate_argnums=(0, 1))
            self._dense_cache[key] = fn
        with self._lock:
            self._data, self._state = fn(self._data, self._state, delta)
        self._serve_bump()

    def _try_device_add(self, delta, expected_shape, option,
                        blocking: bool) -> bool:
        """Route a ``jax.Array`` delta to the device-resident apply.

        Returns False when the delta is host-side or the mode needs the
        host path (BSP buffering, the multi-host collective sum) — the ONE
        spelling of that guard for every dense table ``add``.
        """
        import jax

        if (not isinstance(delta, jax.Array) or self.sync
                or is_multiprocess()):
            return False
        if delta.shape != expected_shape:
            raise ValueError(
                f"delta shape {delta.shape} != {expected_shape}")
        self._apply_dense_device(delta, option)
        if blocking:
            jax.block_until_ready(self._data)
        return True

    def _dense_snapshot(self, live: tuple):
        """Checkpoint the LIVE region (``live``: its extent along each
        axis) of ``_data``/``_state``: padding is an artifact of the mesh
        size and of the device's tiling, and baking it in would pin the
        snapshot to the process/device count that wrote it."""
        import numpy as np

        region = _upto(live)
        return self._locked_read(
            lambda d, s: (np.ascontiguousarray(host_fetch(d)[region]),
                          [np.ascontiguousarray(host_fetch(x)[region])
                           for x in s]))

    def _dense_restore(self, data, state, live: tuple) -> None:
        """Re-pad a live-region snapshot for THIS mesh and place it."""
        import numpy as np

        padded_shape = tuple(self._data.shape)
        region = _upto(live)

        def pad(h):
            out = np.zeros(padded_shape, dtype=self.dtype)
            out[region] = np.asarray(h, dtype=self.dtype)[region]
            return out

        with self._lock:
            self._data = host_put(pad(data), self._sharding)
            self._state = tuple(host_put(pad(s), self._sharding)
                                for s in state)
        self._serve_bump()   # restored timeline: cached reads are void
        if self._compressor is not None:
            # Carried quantization error belongs to the abandoned timeline.
            self._compressor.reset()

    def _locked_read(self, reader):
        """Run ``reader(data, state)`` under the table lock.

        Every eager read of ``_data``/``_state`` must go through this: a
        concurrent add's donated jitted apply deletes the buffer it
        replaces, and launching a gather/fetch on a deleted Array throws.
        (Multi-host callers still follow the SPMD lockstep contract —
        the lock serializes only this process's threads.)
        """
        with self._lock:
            return reader(self._data, self._state)

    def _slice_device(self, limits) -> Any:
        """Device-resident Get: compiled slice to the live region (a fresh
        buffer, so later adds don't mutate what the caller holds).

        Single-controller only: under multi-host the table spans hosts
        (not fully addressable) and the caller could neither ``np.asarray``
        the result nor call out of lockstep safely — use ``get()``."""
        import jax

        if is_multiprocess():
            raise RuntimeError(
                "get(device=True) is a single-controller fast path; under "
                "process_count() > 1 use get() (collective host fetch)")
        fn = self._dense_cache.get(("slice", limits))
        if fn is None:
            fn = jax.jit(lambda d: d[_upto(limits)])
            self._dense_cache[("slice", limits)] = fn
        # Under _lock: a concurrent add's donated apply deletes the buffer
        # it replaces, and launching the slice on a deleted Array throws.
        with self._lock:
            return fn(self._data)

    def close(self) -> None:
        """Unregister from the runtime and drop the device buffers.

        The context registry holds a strong reference to every table (it
        drives flush/checkpoint/shutdown), so ``del table`` alone never
        frees HBM — long-lived processes that create scratch tables (the
        bench, notebooks) call ``close()``.  The name is released for
        reuse; buffered BSP adds are discarded (they could never flush —
        the table left the registry barrier() walks); any later eager op
        on the closed table raises.
        """
        self._ctx.unregister_table(self.table_id)
        self.discard_pending()
        self._closed = True
        with self._lock:
            self._data = None
            self._state = ()
            self._dense_cache.clear()
        if self._serve_cache is not None:
            self._serve_cache.invalidate()

    # -- BSP clock boundary --------------------------------------------------
    def _ssp_defer(self, apply_fn=None) -> None:
        """SSP clock-lag (SURVEY.md §2.9-bis, the SPMD semantic mapping).

        BSP (``staleness=0``): ``apply_fn`` runs now — the flush applies
        at its own barrier.  SSP (``staleness=s``): the apply waits out
        ``s`` further barriers, so a Get at clock *t* is guaranteed all
        adds from clocks ≤ t-1-s (the SSP reader bound) while the last
        *s* clocks' adds may still be pending — the lockstep analog of
        the native plane's per-rank clock vector (``-staleness`` +
        ``MV_Clock``; there stragglers are real, here every rank defers
        identically so the collective applies stay in lockstep).

        Called by each table's ``flush()`` with the pending snapshot
        closed over; the queue is clock-tagged with the barrier that
        buffered it.
        """
        if not self.staleness:
            if apply_fn is not None:
                apply_fn()
            return
        if apply_fn is not None:
            self._stale_queue.append((self._ctx.clock, apply_fn))
        # Drain on EVERY flush (apply_fn=None = nothing new this clock) —
        # an idle clock must still release the backlog it matured.
        ready = [(c, f) for c, f in self._stale_queue
                 if self._ctx.clock - c >= self.staleness]
        self._stale_queue = [(c, f) for c, f in self._stale_queue
                             if self._ctx.clock - c < self.staleness]
        for _, f in sorted(ready, key=lambda cf: cf[0]):
            f()

    def flush(self) -> None:
        """Apply buffered (sync-mode) adds; called by ``barrier()``."""
        raise NotImplementedError

    def discard_pending(self) -> None:
        """Drop buffered (sync-mode) adds without applying them.

        Used by checkpoint restore: deltas buffered before the restore
        belong to the abandoned timeline.
        """
        raise NotImplementedError

    # -- checkpoint hooks (ServerTable::Store/Load parity) -------------------
    def store_state(self) -> Any:
        """Pytree of everything needed to restore the table."""
        raise NotImplementedError

    def load_state(self, state: Any) -> None:
        raise NotImplementedError

    # -- serve layer (docs/serving.md) ---------------------------------------
    @staticmethod
    def serve_key_bucket(key: Any) -> int:
        """Stable bucket of a KV key — crc32, NOT hash(): ranks must
        agree (PYTHONHASHSEED randomizes str hash per process)."""
        import zlib

        return zlib.crc32(repr(key).encode()) % Table.SERVE_BUCKETS

    def _serve_bump(self, buckets=None, keys=None) -> None:
        """Advance the table version after a local apply — the JAX-plane
        analog of the native server's per-apply version stamp.  Bumping
        IS the write-through invalidation: cached entries below the new
        version fail the staleness gate at lookup.  ``buckets`` (row ids
        or key buckets) stamps only the touched buckets.  ``keys`` (the
        touched row ids / KV keys, when the apply is key-granular) feeds
        the workload hot-key tracker — independent of the serve cache,
        which may be disarmed while accounting stays on."""
        if self._workload is not None:
            self._workload.note_add(keys)
        if self._serve_cache is None:
            return
        import numpy as np

        with self._serve_ver_lock:
            self._serve_version += 1
            v = self._serve_version
            if buckets is None:
                if self._serve_buckets is not None:
                    self._serve_buckets[:] = v
                return
            if self._serve_buckets is None:
                # Lazily created on the FIRST bucket-granular bump: seed
                # every bucket with the pre-bump version, not zero —
                # whole-table bumps (dense adds, load_state) that ran
                # while the array was None must stay visible to the
                # staleness gate, else entries cached before them would
                # hit forever.  (The native ServerTable sidesteps this:
                # its bucket array exists from construction.)
                self._serve_buckets = np.full(self.SERVE_BUCKETS, v - 1,
                                              np.int64)
            idx = np.asarray(list(buckets), np.int64) % self.SERVE_BUCKETS
            self._serve_buckets[idx] = v

    def note_routing_epoch(self, epoch: int) -> None:
        """Adopt a fleet routing-epoch observation (docs/replication.md).

        Callers bridging this table to the native serve plane (demo
        drivers, apps gluing both planes) feed the epoch from
        ``NativeRuntime.routing_epoch()`` / an ops ``"replication"``
        scrape here; a FLIP means a shard was promoted or joined, so
        every cached serve entry — stamped under the previous shard
        owner's version timeline — is voided by a whole-table bump.
        Monotonic: stale observations are ignored (the PR 4 max-merge
        discipline).  MV017's rule in one line: never carry a cached
        shard-routing decision across a wire call without re-checking
        this epoch."""
        with self._serve_ver_lock:
            if epoch <= self._routing_epoch:
                return
            self._routing_epoch = int(epoch)
        self._serve_bump()  # route flip = cached reads are void

    @property
    def routing_epoch(self) -> int:
        """Last adopted fleet routing epoch (0 = registration map)."""
        with self._serve_ver_lock:
            return self._routing_epoch

    def _serve_current_many(self, buckets):
        """Per-bucket version estimates for a batch of reads — ONE lock
        acquisition for the whole id set (the row-granular cache gates
        each row on its own bucket, so per-row ``_serve_current`` calls
        would pay the lock k times)."""
        import numpy as np

        idx = np.asarray([int(b) for b in buckets], np.int64)
        with self._serve_ver_lock:
            if self._serve_buckets is None or idx.size == 0:
                return np.full(idx.shape, self._serve_version, np.int64)
            return self._serve_buckets[idx % self.SERVE_BUCKETS].copy()

    def _serve_current(self, buckets=None) -> int:
        """Version gating a read: table version, or the max over the
        touched buckets (adds elsewhere don't invalidate this read)."""
        import numpy as np

        with self._serve_ver_lock:
            if buckets is None or self._serve_buckets is None:
                return self._serve_version
            idx = np.asarray(list(buckets), np.int64)
            if idx.size == 0:
                return 0
            return int(self._serve_buckets[idx % self.SERVE_BUCKETS].max())

    def workload_report(self) -> dict:
        """Per-table workload report (docs/observability.md): the same
        shape as one entry of the native ``"hotkeys"`` OpsQuery kind —
        get/add totals, bucket-load skew ratio, top-K hot keys with
        count-min estimates.  ``{"armed": False}`` when disabled."""
        if self._workload is None:
            return {"id": self.table_id, "armed": False}
        out = {"id": self.table_id, "armed": True}
        out.update(self._workload.report())
        return out

    def _serve_read(self, key: tuple, fetch, buckets=None,
                    collective_safe: bool = True, copy=None, keys=None):
        """Cache + coalesce an eager host read (docs/serving.md).

        ``fetch`` is the full existing read path (including any
        multi-host collective); it runs at most once per coalescing
        window.  ``collective_safe=False`` marks reads whose cache keys
        can DIFFER per rank (row-id / key-set reads): a rank-local hit
        there would break the lockstep fetch collective, so they bypass
        the cache under ``process_count() > 1``.  ``copy`` clones a
        value on the cache boundary (default: ndarray ``.copy()``) so
        caller mutation cannot corrupt the cached copy.  ``keys`` (the
        touched row ids / KV keys) feeds the workload hot-key tracker
        regardless of whether the cache is armed.
        """
        if self._workload is not None:
            self._workload.note_get(keys)
        cache = self._serve_cache
        if cache is None or (not collective_safe and is_multiprocess()):
            return fetch()
        if copy is None:
            def copy(v):
                return v.copy()
        cur = self._serve_current(buckets)
        forced = False
        try:
            # Chaos seam: an injected serve.stale forces this read to
            # miss (tests script staleness storms without real adds).
            fault.inject("serve.stale")
        except fault.FaultError:
            forced = True
        if not forced:
            hit = cache.lookup(key, min_version=cur - self._serve_staleness)
            if hit is not None:
                return copy(hit[0])
        else:
            metrics.counter("serve.cache.miss").inc()

        def execute(items):
            out = fetch()
            return [out] * len(items)   # one fetch serves every waiter

        with tracing.span("serve::table_get", table=self.name,
                          key=str(key)):
            val = self._serve_coalescer.submit((id(self),) + key, None,
                                               execute)
        # Stamp with the PRE-fetch version: the fetch ran after the
        # estimate, so the data is at least that new (a post-fetch stamp
        # could mark pre-add data as post-add fresh).  Store the fetched
        # value ITSELF and copy once on the way out — nothing else holds
        # `val` mutably (every coalesced waiter runs this same tail and
        # takes its own copy; hits copy at lookup), so the old
        # store-a-copy-then-return-a-copy pair was one redundant
        # full-payload copy per miss.
        cache.store(key, val, cur)
        return copy(val)

    def _serve_read_rows(self, kind, keys, fetch_subset, buckets=None,
                         note_keys=None):
        """Row-granular serve cache (docs/embedding.md).

        Per-KEY cache entries ``(id(self), kind, key)``, each gated by
        its OWN bucket version — a cached hot row keeps hitting across
        different requested id sets and across adds to other buckets,
        and a miss fetches only the missing keys (never the whole set,
        never the whole table).  ``fetch_subset(sub)`` returns one value
        per key of ``sub`` (deduplicated, arbitrary order preserved).

        Returns the per-key value list in request order, or ``None``
        when this path is disarmed — serve cache off, ``-serve_row_cache
        =false``, or multi-host (per-rank key sets would break the
        lockstep fetch collective; the caller falls back to the id-set
        path, which bypasses correctly).  Returned values are the CACHED
        objects (stored read-only): the caller copies at its own
        boundary (np.stack / per-value .copy()).

        Miss accounting mirrors the PR 4 review fix: nothing accrues
        unless this path is ARMED — a disabled row cache must not count
        chaos-forced misses (the regression tests/test_embedding.py
        pins this).
        """
        cache = self._serve_cache
        if (cache is None or not self._serve_row_cache
                or is_multiprocess()):
            return None
        if self._workload is not None:
            self._workload.note_get(
                note_keys if note_keys is not None
                else [int(k) for k in keys])
        import numpy as np

        keys_list = list(keys)
        bucket_list = list(buckets) if buckets is not None else keys_list
        vers = self._serve_current_many(bucket_list)
        forced = False
        try:
            # Chaos seam: an injected serve.stale forces this read to
            # miss wholesale (tests script staleness storms) — counted
            # only here, past the armed gate.
            fault.inject("serve.stale")
        except fault.FaultError:
            forced = True
            metrics.counter("serve.cache.miss").inc()
        values: dict = {}
        missing = []
        miss_vers: dict = {}
        first_idx: dict = {}
        for i, k in enumerate(keys_list):
            if k not in first_idx:
                first_idx[k] = i  # order-preserving dedup
        uniq = list(first_idx)
        if forced:
            missing = uniq
            miss_vers = {k: int(vers[first_idx[k]]) for k in uniq}
        else:
            # ONE lock + counter update for the whole id set
            # (VersionedLRUCache.lookup_many) — per-key lookup() calls
            # would pay the lock and the metrics registry k times.
            got = cache.lookup_many(
                [(id(self), kind, k) for k in uniq],
                [int(vers[first_idx[k]]) - self._serve_staleness
                 for k in uniq])
            for k, v in zip(uniq, got):
                if v is not None:
                    values[k] = v
                else:
                    missing.append(k)
                    # Pre-fetch stamp per key: the fetch runs after
                    # this estimate, so the data is at least this new.
                    miss_vers[k] = int(vers[first_idx[k]])
        if missing:
            def execute(items):
                # Coalesced miss fetch: concurrent readers' missing
                # sets union into ONE subset fetch (the ServeClient
                # row-get discipline, host-local edition).
                union = []
                seen = set()
                for it in items:
                    for k in it:
                        if k not in seen:
                            seen.add(k)
                            union.append(k)
                fetched = fetch_subset(union)
                lut = dict(zip(union, fetched))
                return [[lut[k] for k in it] for it in items]

            with tracing.span("serve::row_get", table=self.name,
                              k=len(missing)):
                got = self._serve_coalescer.submit(
                    (id(self), kind, "rows"), missing, execute)
            for k, v in zip(missing, got):
                if isinstance(v, np.ndarray):
                    # Loud ValueError on any aliasing slip instead of
                    # silent cache corruption (the ServeClient
                    # discipline); callers copy at their boundary.
                    v = v.copy()
                    v.flags.writeable = False
                cache.store((id(self), kind, k), v, miss_vers[k])
                values[k] = v
        return [values[k] for k in keys_list]

    # -- host-bridge borrow/out= protocol (docs/host_bridge.md) --------------
    def _coerce_delta(self, delta, borrow: bool):
        """THE one coercion gate of every eager add path.

        ``borrow=False`` (default): the defensive ``np.asarray`` —
        converts dtype/layout as needed (possibly copying).
        ``borrow=True``: the caller guarantees ``delta`` is already
        this table's dtype, C-contiguous, and will not be mutated while
        buffered (BSP) or in flight — the path then stores/ships it
        WITHOUT the astype/copy churn (mvlint MV012's arena protocol);
        a wrong layout raises instead of silently copying, so the fast
        path cannot quietly decay into the slow one."""
        import numpy as np

        if not borrow:
            return np.asarray(delta, dtype=self.dtype)
        if not isinstance(delta, np.ndarray):
            raise TypeError(
                f"borrow=True needs an ndarray delta, got {type(delta)!r}")
        if delta.dtype != self.dtype:
            raise ValueError(
                f"borrow=True: delta dtype {delta.dtype} != table dtype "
                f"{self.dtype} — the borrow protocol never converts")
        if not delta.flags["C_CONTIGUOUS"]:
            raise ValueError(
                "borrow=True: delta is not C-contiguous — the borrow "
                "protocol never copies")
        return delta

    @staticmethod
    def _fill_out(out, val):
        """``out=`` tail of the eager get paths: fill the caller's
        preallocated buffer (killing the per-call allocation) or hand
        back ``val`` unchanged."""
        if out is None:
            return val
        import numpy as np

        np.copyto(out, val)
        return out

    def _monitor(self, op: str):
        # Every public eager op opens with this — it doubles as the
        # closed-table guard (a closed table's sync buffers would
        # otherwise swallow adds silently) and as the chaos seam: the
        # fault injector can script a Get/Add failure here exactly where
        # a real transport error would surface (tests/test_fault.py).
        if self._closed:
            raise RuntimeError(
                f"table '{self.name}' is closed (close() was called)")
        fault.inject(f"table.{op}")
        return dashboard.monitor(f"{type(self).__name__}::{op}")
