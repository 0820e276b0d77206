"""multiverso_tpu — a TPU-native parameter-server-capability framework.

A ground-up JAX/XLA re-design of the capabilities of Multiverso (Microsoft
DMTK's parameter server; reference fork ``xuehui1991/multiverso``, surveyed
in SURVEY.md): distributed model state in Array / Matrix / SparseMatrix /
KV tables with push-pull ``Add``/``Get``, server-side updaters
(SGD/AdaGrad/Momentum/SmoothGradient), BSP and ASP data-parallel training,
a flat C API with Python and Torch bindings, and the bundled applications.

The worker↔server message fabric of the reference collapses into sharded
``jax.Array``s on a device mesh with XLA collectives over ICI; what stays on
the host is the control plane (init/barrier/flags/logging/dashboard) plus a
native C runtime for FFI parity.

Top-level API mirrors the reference Python binding
(``binding/python/multiverso/__init__.py``; SURVEY.md §2.28–2.29).
"""

from __future__ import annotations

import time as _time

_IMPORT_T0 = _time.perf_counter()    # monitor ``mv::import``, booked below

from . import (checkpoint, config, dashboard, fault, io, metrics, serve,
               tracing)
from .core import (
    BarrierTimeout,
    barrier,
    clock,
    get_context,
    init,
    initialized,
    is_master_worker,
    num_replicas,
    server_id,
    servers_num,
    shutdown,
    worker_id,
    workers_num,
)
from .log import Log
from .tables import (
    ArrayTable,
    KVTable,
    MatrixTable,
    SparseMatrixTable,
    Table,
    create_table,
)
from .updaters import AddOption, GetOption, get_updater

__version__ = "0.1.0"

# ---------------------------------------------------------------------------
# Binding-parity handler aliases (reference ``tables.py``: TableHandler /
# ArrayTableHandler / MatrixTableHandler with .get()/.add(data, sync=...)).
# The TPU tables already speak that exact surface, so handlers are the
# tables themselves.
# ---------------------------------------------------------------------------
TableHandler = Table
ArrayTableHandler = ArrayTable


class MatrixTableHandler(MatrixTable):
    """Reference ``MatrixTableHandler`` surface (SURVEY.md §2.29).

    Adds the reference's ``*_by_rows`` method names over MatrixTable.
    """

    def get_all(self):
        return self.get()

    def add_all(self, delta, option=None, sync: bool = False):
        return self.add(delta, option=option, sync=sync)

    def get_by_rows(self, row_ids, option=None):
        return self.get_rows(row_ids, option=option)

    def add_by_rows(self, delta, row_ids, option=None, sync: bool = False):
        return self.add_rows(row_ids, delta, option=option, sync=sync)


__all__ = [
    "init", "shutdown", "initialized", "barrier", "clock",
    "worker_id", "workers_num", "server_id", "servers_num",
    "is_master_worker", "num_replicas", "get_context",
    "Table", "ArrayTable", "MatrixTable", "SparseMatrixTable", "KVTable",
    "create_table", "TableHandler", "ArrayTableHandler", "MatrixTableHandler",
    "AddOption", "GetOption", "get_updater",
    "config", "dashboard", "Log", "checkpoint", "io", "fault",
    "metrics", "tracing", "BarrierTimeout",
]

# What importing the package cost this process (docs/observability.md,
# "Start-up"); jax's own import is in it only if nothing imported jax first.
dashboard.get_monitor("mv::import").observe(
    _time.perf_counter() - _IMPORT_T0)
