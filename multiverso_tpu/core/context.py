"""Core runtime context — the TPU-native successor of the reference ``Zoo``.

Reference semantics (SURVEY.md §2.2, §3.1): ``Zoo::Start`` parses flags,
initializes the transport (MPI/ZMQ), spawns the Communicator / Worker /
Server / Controller actor threads, registers every node with rank 0, and
barriers.  ``Zoo::Stop`` barriers, joins actors, dumps the Dashboard, and
finalizes the transport.

TPU-native redesign: there are no server processes and no point-to-point
transport.  Model state lives in sharded ``jax.Array``s over a
``jax.sharding.Mesh``; the push-pull message path compiles to XLA
collectives over ICI.  What remains on the host is the control plane:

- ``init()``      → flag parsing, optional ``jax.distributed.initialize``
                    (DCN, multi-host), mesh construction, table registry.
- ``barrier()``   → ``multihost_utils.sync_global_devices`` across hosts
                    (the Controller's Control_Barrier round-trip) + the BSP
                    clock tick that sync-mode tables key on.
- ``shutdown()``  → final barrier, Dashboard dump, registry teardown.

Identity mapping (kept name-compatible with the reference C API):

- a reference *worker process*  ↔ a controller **host process**
  (``worker_id() == jax.process_index()``): the unit that loads a data shard.
- a reference *server process*  ↔ the same host (every device holds table
  shards), so ``server_id() == worker_id()`` under Role.ALL, matching the
  reference's default role assignment.
- device-level data parallelism (the mesh's worker axis) is *inside* the
  compiled step; its width is exposed as ``num_replicas()``.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

import jax
import numpy as np

from .. import compile_cache, config, dashboard, metrics, tracing
from ..log import Log

__all__ = [
    "Role", "Context", "BarrierTimeout", "init", "shutdown", "initialized",
    "barrier", "get_context", "worker_id", "workers_num", "server_id",
    "servers_num", "is_master_worker", "num_replicas", "clock",
]


class BarrierTimeout(TimeoutError):
    """A host rendezvous did not complete within its deadline.

    Raised instead of blocking forever when ``barrier()``/``host_sync``
    is given a timeout (kwarg or the ``barrier_timeout_ms`` flag) and a
    peer process never arrives — the SPMD-plane analog of the native
    runtime's ``-barrier_timeout_ms`` (C API rc ``-3``).  NOTE the
    underlying collective cannot be cancelled: the watcher thread stays
    parked in it, so treat this as fatal for the job (checkpoint and
    exit), not as something to retry.
    """


class Role:
    """Role bitmask — parity with reference ``node.h`` (SURVEY.md §2.5)."""

    NONE = 0
    WORKER = 1
    SERVER = 2
    ALL = 3


@dataclass
class Node:
    """Per-process node info (reference ``Node``; SURVEY.md §2.5)."""

    rank: int
    size: int
    role: int = Role.ALL

    @property
    def is_worker(self) -> bool:
        return bool(self.role & Role.WORKER)

    @property
    def is_server(self) -> bool:
        return bool(self.role & Role.SERVER)


class Context:
    """Singleton runtime registry (reference ``Zoo``; SURVEY.md §2.2)."""

    def __init__(self, mesh: jax.sharding.Mesh, node: Node, sync: bool,
                 updater_type: str):
        self.mesh = mesh
        self.node = node
        self.sync = sync
        self.updater_type = updater_type
        self.clock = 0
        self._tables: Dict[int, Any] = {}
        self._next_table_id = 0
        self._lock = threading.Lock()

    # -- table registry (Zoo::RegisterTable) --------------------------------
    def register_table(self, table: Any) -> int:
        with self._lock:
            tid = self._next_table_id
            self._next_table_id += 1
            self._tables[tid] = table
            return tid

    def unregister_table(self, table_id: int) -> None:
        with self._lock:
            self._tables.pop(table_id, None)

    def table(self, table_id: int) -> Any:
        return self._tables[table_id]

    def tables(self) -> List[Any]:
        return list(self._tables.values())

    # -- barrier / clock ----------------------------------------------------
    def host_sync(self, name: str,
                  timeout_s: Optional[float] = None) -> None:
        """Cross-host rendezvous WITHOUT the BSP clock tick / flush.

        For control-plane sync points (checkpointing) that must not apply
        pending sync-mode adds or advance the training clock.

        ``timeout_s`` (default: the ``barrier_timeout_ms`` flag; 0 =
        wait forever) bounds the wait: a peer that never arrives raises
        :class:`BarrierTimeout` naming the sync point instead of hanging
        the job.  The wait runs on a watcher thread because the
        underlying collective has no cancellation — on timeout that
        thread is abandoned (daemon) and the error documents the job as
        unrecoverable-but-diagnosable.
        """
        from .. import fault

        if timeout_s is None:
            ms = int(config.get("barrier_timeout_ms"))
            timeout_s = ms / 1e3 if ms > 0 else None

        def wait() -> None:
            # Chaos seam: the injector can delay (simulating a straggler
            # peer) or fail this rendezvous (tests/test_fault.py).
            fault.inject("barrier")
            if jax.process_count() > 1:
                from jax.experimental import multihost_utils

                multihost_utils.sync_global_devices(name)

        if timeout_s is None:
            wait()
            return
        done = threading.Event()
        err: list = []

        def body() -> None:
            try:
                wait()
            except BaseException as exc:  # re-raised on the caller
                err.append(exc)
            finally:
                done.set()

        t = threading.Thread(target=body, name="mvtpu-host-sync",
                             daemon=True)
        t.start()
        if not done.wait(timeout_s):
            # Flight-recorder trigger (docs/observability.md): the
            # moment the job becomes unrecoverable is the moment the
            # black box must hit disk — before the raise unwinds.
            from ..ops.flight_recorder import recorder

            recorder.trigger(f"barrier_timeout: host_sync '{name}' "
                             f"after {timeout_s:.3f}s")
            raise BarrierTimeout(
                f"host_sync '{name}' timed out after {timeout_s:.3f}s "
                f"waiting for {jax.process_count()} process(es) — an "
                f"unresponsive peer; treat as fatal (the collective "
                f"cannot be cancelled)")
        if err:
            raise err[0]

    def barrier(self, name: Optional[str] = None,
                timeout_s: Optional[float] = None) -> None:
        with dashboard.monitor("Zoo::Barrier"):
            self.host_sync(name or f"mvtpu_barrier_{self.clock}",
                           timeout_s=timeout_s)
            self.clock += 1
            for t in self.tables():
                flush = getattr(t, "flush", None)
                if flush is not None:
                    flush()


_LOCK = threading.Lock()
_CONTEXT: Optional[Context] = None


def _default_mesh(axis_name: str = "worker") -> jax.sharding.Mesh:
    devices = np.asarray(jax.devices())
    return jax.sharding.Mesh(devices, (axis_name,))


def init(args: Optional[List[str]] = None,
         sync: Optional[bool] = None,
         updater_type: Optional[str] = None,
         mesh: Optional[jax.sharding.Mesh] = None,
         role: int = Role.ALL,
         distributed: bool = False,
         **distributed_kwargs) -> Context:
    """Start the runtime (reference ``MV_Init`` → ``Zoo::Start``; §3.1).

    ``args`` takes reference-style ``-flag=value`` argv.  Keyword arguments
    override parsed flags.  ``distributed=True`` calls
    ``jax.distributed.initialize`` for multi-host (DCN) jobs before building
    the mesh — the analog of the transport Init + rank-0 registration.
    """
    global _CONTEXT
    with _LOCK:
        if _CONTEXT is not None:
            Log.info("multiverso_tpu.init: already initialized; reusing context")
            return _CONTEXT

        # CLI args mutate the process-global flag registry (reference
        # semantics); keyword overrides are per-lifecycle only, so a
        # sync=True passed to one init() cannot leak into the next.
        config.parse_cmd_flags(args)
        sync_val = bool(config.get("sync")) if sync is None else bool(sync)
        updater_val = (str(config.get("updater_type"))
                       if updater_type is None else str(updater_type))

        from ..log import configure as log_configure

        log_configure(config.get("log_level"), config.get("log_file"))

        compile_cache.configure()

        if distributed and not jax.distributed.is_initialized():
            # Multi-host bring-up (DCN): the reference's NetInterface::Init +
            # Control_Register handshake collapses into this one call. Must
            # run before anything touches the backend (so no process_count()
            # guard here).  An environment that already initialized is the
            # only thing tolerated: a failure raises, because N ranks that
            # carried on would each train alone.
            jax.distributed.initialize(**distributed_kwargs)

        if mesh is None:
            mesh = _default_mesh()

        node = Node(rank=jax.process_index(), size=jax.process_count(),
                    role=role)

        # Observability (docs/observability.md): -trace_dir arms span
        # recording (shutdown writes trace_rank<r>.json there);
        # -metrics_flush_ms starts the periodic Prometheus exporter.
        # After the distributed bring-up so process_index() is final.
        trace_dir = str(config.get("trace_dir"))
        if trace_dir:
            tracing.enable(rank=node.rank)
        # Flight recorder (docs/observability.md): always-on bounded
        # ring; the rank pin names the blackbox_rank<r>.json dump a
        # failure trigger (BarrierTimeout, CheckpointCorrupt) writes.
        from ..ops.flight_recorder import recorder as _recorder

        _recorder.attach(rank=node.rank)
        _recorder.record("lifecycle",
                         f"init rank {node.rank}/{node.size}")
        # Latency plane (docs/observability.md): -profile_hz arms the
        # Python sampler thread; its folded stacks land in the trace
        # export at shutdown beside the spans.
        profile_hz = int(config.get("profile_hz"))
        if profile_hz > 0:
            from .. import profiler as _profiler

            _profiler.start(profile_hz)
        flush_ms = int(config.get("metrics_flush_ms"))
        metrics.set_history_depth(int(config.get("metrics_history")))
        if flush_ms > 0:
            import os

            metrics.start_flush(
                flush_ms,
                path=os.path.join(trace_dir,
                                  f"metrics_rank{node.rank}.prom")
                if trace_dir else None)
            # Health plane (docs/observability.md "health plane"):
            # -health_rules arms the default SLO/alert pack on the
            # flush cadence — rules can only evaluate when flushes
            # actually happen, so the gate rides flush_ms.
            if bool(config.get("health_rules")):
                from .. import health as _health

                _health.arm()

        _CONTEXT = Context(mesh=mesh, node=node,
                           sync=sync_val,
                           updater_type=updater_val)
        Log.info(
            "multiverso_tpu initialized: %d process(es), %d device(s), "
            "mesh axes %s, sync=%s, updater=%s",
            node.size, len(jax.devices()), dict(mesh.shape),
            _CONTEXT.sync, _CONTEXT.updater_type,
        )
        _CONTEXT.barrier("mvtpu_init")
        return _CONTEXT


def shutdown(finalize: bool = True) -> None:
    """Stop the runtime (reference ``MV_ShutDown`` → ``Zoo::Stop``; §3.5)."""
    global _CONTEXT
    with _LOCK:
        if _CONTEXT is None:
            return
        from ..ops.flight_recorder import recorder as _recorder

        _recorder.record("lifecycle",
                         f"shutdown rank {_CONTEXT.node.rank}")
        _CONTEXT.barrier("mvtpu_shutdown")
        # Observability teardown: health evaluator off BEFORE the final
        # flush (an alert must not fire against a half-torn-down rank),
        # then the last flush, then the span export (-trace_dir), then
        # the classic Dashboard dump — which now prints percentiles
        # from the same registry.
        from .. import health as _health

        _health.disarm()
        metrics.stop_flush()
        # Profiler down BEFORE the trace export so its folded stacks
        # ride trace_rank<r>.json (stop() folds them into the buffer).
        from .. import profiler as _profiler

        _profiler.stop(to_trace=True)
        trace_dir = str(config.get("trace_dir"))
        if trace_dir and tracing.enabled():
            import os

            os.makedirs(trace_dir, exist_ok=True)
            tracing.save(tracing.default_trace_path(trace_dir))
        dashboard.report(log=True)
        compile_cache.report()
        if finalize:
            dashboard.reset()
            tracing.clear()
        _CONTEXT = None


def initialized() -> bool:
    return _CONTEXT is not None


def get_context() -> Context:
    if _CONTEXT is None:
        raise RuntimeError(
            "multiverso_tpu is not initialized; call multiverso_tpu.init()")
    return _CONTEXT


def barrier(timeout_s: Optional[float] = None) -> None:
    get_context().barrier(timeout_s=timeout_s)


def clock() -> int:
    return get_context().clock


def worker_id() -> int:
    """Rank of this host's worker role (reference ``MV_WorkerId``)."""
    return get_context().node.rank


def workers_num() -> int:
    """Number of worker hosts (reference ``MV_NumWorkers``)."""
    return get_context().node.size


def server_id() -> int:
    """Under Role.ALL every host co-hosts server shards (``MV_ServerId``)."""
    node = get_context().node
    return node.rank if node.is_server else -1


def servers_num() -> int:
    return get_context().node.size


def is_master_worker() -> bool:
    return worker_id() == 0


def num_replicas() -> int:
    """Device-level data-parallel width inside the compiled step.

    The size of the mesh's data-parallel axis (named ``worker``, ``dp`` or
    ``data``); for a mesh with no such axis, the full device count (a pure
    model-parallel mesh has one replica per full model, but tables still
    shard over every device).
    """
    ctx = get_context()
    for axis in ("worker", "dp", "data"):
        if axis in ctx.mesh.shape:
            return int(ctx.mesh.shape[axis])
    return int(np.prod(list(ctx.mesh.shape.values())))
