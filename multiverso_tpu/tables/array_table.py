"""ArrayTable — dense 1-D parameter vector.

Reference (SURVEY.md §2.11, ``table/array_table.h``): contiguous float/int
vector evenly sharded over server processes; workers ``Get`` the whole array
and ``Add`` whole-array deltas; the server applies the Updater per shard.

TPU-native: the vector is ONE ``jax.Array`` sharded over the table mesh
(each device holds the contiguous chunk a reference server would).  ``Get``
is a device→host gather; ``Add`` is a jitted donate-in-place updater call —
on a multi-device mesh XLA lays the delta scatter + update on each shard's
home device, which is exactly the reference's server-side `ProcessAdd` with
the network replaced by ICI.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..parallel.sharding import shard_along, table_mesh
from ..updaters import AddOption
from .base import Table, host_fetch, host_put

__all__ = ["ArrayTable"]


class ArrayTable(Table):
    kind = "array"

    def __init__(self, size: int, dtype: Any = jnp.float32,
                 init: Optional[np.ndarray] = None, **kw):
        super().__init__(**kw)
        self.size = int(size)
        self.dtype = jnp.dtype(dtype)
        self._mesh = table_mesh(self._ctx.mesh)
        n = self._mesh.devices.size
        self._padded = ((self.size + n - 1) // n) * n
        self._sharding = shard_along(self._mesh, ndim=1, dim=0)

        host = np.zeros(self._padded, dtype=self.dtype)
        if init is not None:
            host[: self.size] = np.asarray(init, dtype=self.dtype)
        self._data = host_put(host, self._sharding)
        self._state = tuple(
            host_put(np.zeros(self._padded, dtype=self.dtype),
                     self._sharding)
            for _ in range(self.updater.num_slots))
        # BSP clock buffers, bucketed per AddOption so a flush applies each
        # option's aggregate with the right hyper-parameters.
        self._pending: Dict[Optional[AddOption], np.ndarray] = {}
        # Options whose buffered delta is a BORROWED caller array (no
        # defensive copy, docs/host_bridge.md): a second add to the same
        # option must not += into the caller's memory.
        self._pending_borrowed: set = set()

    # ------------------------------------------------------------------ Get
    def get(self, option=None, device: bool = False, out=None):
        """Pull the whole array (reference ``ArrayWorker<T>::Get``; §3.2).

        ``device=True`` returns a fresh device ``jax.Array`` instead of a
        host copy — the TPU-native Get for callers whose next op runs on
        device (no wire hop; pairs with passing a device delta to ``add``).
        ``out=`` fills a preallocated host buffer instead of allocating
        one per call (the host-bridge out= protocol, docs/host_bridge.md).
        """
        with self._monitor("Get"):
            if device:
                if out is not None:
                    raise ValueError("out= is a host-path argument")
                return self._slice_device((self.size,))
            # Serve layer (docs/serving.md): repeat host reads within the
            # version-staleness bound serve from the client cache;
            # concurrent misses coalesce into one fetch.  No-op unless
            # -serve_cache_entries armed the cache.
            return self._fill_out(out, self._serve_read(
                ("get",),
                lambda: self._locked_read(
                    lambda d, s: host_fetch(d))[: self.size]))

    # ------------------------------------------------------------------ Add
    def add(self, delta, option: Optional[AddOption] = None,
            sync: bool = False, compress: Optional[str] = None,
            borrow: bool = False) -> None:
        """Push a delta/gradient (reference ``ArrayWorker<T>::Add``; §3.3).

        ``delta`` is [size] or [k, size] (stacked per-worker contributions,
        summed before the updater — the server receiving k Adds).  ``sync``
        blocks until the device commit completes (the reference's blocking
        Add vs AddAsync).  ``compress="1bit"`` sends sign bits + scales
        with error feedback (1/32 the wire bytes; lossy per add, SGD-safe
        — SURVEY.md §5 quantization lineage).  ``borrow=True``: ``delta``
        is already this table's dtype/C layout and will not be mutated
        until applied — the path skips the defensive astype/copy churn
        (docs/host_bridge.md; wrong layouts raise instead of copying).
        """
        with self._monitor("Add"):
            if compress is None and isinstance(delta, jax.Array) \
                    and delta.ndim == 2:
                delta = delta.sum(axis=0)      # worker stack, on device
            if compress is None and self._try_device_add(
                    delta, (self.size,), option, sync):
                return
            if compress is None:
                # -wire_codec=1bit: host dense adds default to the 1-bit
                # wire format (docs/wire_compression.md).
                compress = self._wire_compress_default()
            delta = self._coerce_delta(delta, borrow)
            if delta.ndim == 2:
                delta = delta.sum(axis=0)
            if delta.shape != (self.size,):
                raise ValueError(
                    f"delta shape {delta.shape} != ({self.size},)")
            if compress is not None:
                self._add_compressed(delta, option, compress, sync)
                return
            if self.sync:
                # BSP: buffer until the clock boundary (barrier → flush).
                # Borrowed deltas buffer WITHOUT the defensive copy; a
                # second add to the same option must then allocate a
                # fresh sum instead of += into the caller's memory.
                with self._lock:
                    if option in self._pending:
                        if option in self._pending_borrowed:
                            self._pending[option] = (
                                self._pending[option] + delta)
                            self._pending_borrowed.discard(option)
                        else:
                            self._pending[option] += delta
                    elif borrow:
                        self._pending[option] = delta
                        self._pending_borrowed.add(option)
                    else:
                        self._pending[option] = delta.astype(
                            self.dtype, copy=True)
                return
            self._apply_now(delta, option)
            if sync:
                jax.block_until_ready(self._data)

    def flush(self) -> None:
        with self._lock:
            pending, self._pending = self._pending, {}
            self._pending_borrowed = set()

        def apply(pending=pending):
            for option, delta in pending.items():
                self._apply_now(delta, option)

        self._ssp_defer(apply if pending else None)

    def discard_pending(self) -> None:
        with self._lock:
            self._pending = {}
            self._pending_borrowed = set()
            self._stale_queue = []

    def _apply_now(self, delta: np.ndarray, option: Optional[AddOption]) -> None:
        self._apply_dense_padded(delta, option)

    # ------------------------------------------------- fused (in-jit) path
    def raw_value(self) -> Tuple[jax.Array, Tuple[jax.Array, ...]]:
        """Hand the sharded arrays to a jitted step (TPU-native hot loop)."""
        return self._data, self._state

    def raw_assign(self, data: jax.Array,
                   state: Optional[Tuple[jax.Array, ...]] = None) -> None:
        self._data = data
        if state is not None:
            self._state = state

    @property
    def sharding(self):
        return self._sharding

    # ------------------------------------------------------------ checkpoint
    def store_state(self) -> Any:
        data, state = self._dense_snapshot((self.size,))
        return {
            "kind": self.kind,
            "size": self.size,
            "data": data,
            "state": state,
        }

    def load_state(self, snap: Any) -> None:
        assert snap["kind"] == self.kind and snap["size"] == self.size
        self._dense_restore(snap["data"], snap["state"], (self.size,))
