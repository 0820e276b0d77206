"""A traced window by the names of a model whose layers differ in kind: the
scopes ``attn.full`` and ``attn.sliding`` (inside ``attn``) and
``moe.shared`` (inside ``mlp``) of ``multiverso_tpu/models``, the three
windowed flash kernels ``flash_win_fwd``, ``flash_win_bwd_dq`` and
``flash_win_bwd_dkv`` of ``ops/flash_attention.py``, and XLA's grouped-matmul
calls (``trace/moe.py``).

``program.SCOPES`` and ``program.KERNELS`` are constants that do not hold
these names, so the readers that need them share this walk of the run's
trace (a fourth one; to be folded into ``program.py`` by a ``benchmark`` PR,
``PERF.md`` section 7).  An instruction is booked to the innermost of
``SCOPES`` in its ``op_name``, whatever the phase, and a Mosaic custom call
to the innermost of ``KERNELS``.  The roofline shares are computed from the
facts the runner ``lm_train_kinds`` gives, which count the routes the step
itself counted.

A program without any of this (the parent of the PR that added it, a model
whose layers are all alike) gives ``None`` and the readers leave their
metric out.
"""

from __future__ import annotations

import functools
import glob
import os
from dataclasses import dataclass
from typing import Dict, Optional

from benchmarks.trace import program
from benchmarks.trace.reduce import (WINDOW_SPAN, _clip, classify,
                                     is_grouped_matmul, load_xplane,
                                     self_times)

__all__ = ["SCOPES", "KERNELS", "Kinds", "summarize", "of_reading",
           "scope_ms_per_step", "kernel_roofline", "gmm_held_roofline",
           "held_route_share"]

SCOPES = ("attn.full", "attn.sliding", "moe.shared")
KERNELS = ("flash_win_fwd", "flash_win_bwd_dq", "flash_win_bwd_dkv")
# the runner's fact that holds a kernel's least bytes
_KERNEL_PART = {"flash_win_fwd": "fwd"}


@dataclass
class Kinds:
    """Seconds of device self time in the window, means over the chips."""
    step_programs: int
    busy_s: float
    by_scope_s: Dict[str, float]
    by_kernel_s: Dict[str, float]
    grouped_matmul_s: float


def summarize(trace, index) -> Optional[Kinds]:
    windows = [e for e in trace.host if e.name == WINDOW_SPAN]
    if not trace.devices or not windows:
        return None
    t0 = min(w.start for w in windows)
    t1 = max(w.end for w in windows)
    chips = len(trace.devices)
    scopes = {s: 0.0 for s in SCOPES}
    kernels = {k: 0.0 for k in KERNELS}
    busy = grouped = 0.0
    programs = 0
    for dev in trace.devices.values():
        for e, self_ns in self_times(_clip(dev.ops, t0, t1)):
            busy += self_ns
            op_name = index.op_name(e.name)
            where = program.scope(op_name, among=SCOPES)
            if where is not None:
                scopes[where] += self_ns
            if is_grouped_matmul(e.name):
                grouped += self_ns
            elif classify(e.name) == "mosaic":
                which = program.scope(op_name, among=KERNELS)
                if which is not None:
                    kernels[which] += self_ns
        programs += sum(1 for e in _clip(dev.modules, t0, t1)
                        if e.name.startswith("jit_step"))
    if not any(scopes.values()) and not any(kernels.values()):
        return None
    return Kinds(step_programs=programs // chips, busy_s=busy / chips / 1e9,
                 by_scope_s={k: v / chips / 1e9 for k, v in scopes.items()},
                 by_kernel_s={k: v / chips / 1e9 for k, v in kernels.items()},
                 grouped_matmul_s=grouped / chips / 1e9)


@functools.lru_cache(maxsize=1)
def _of_file(path: str, mtime: float) -> Optional[Kinds]:
    return summarize(load_xplane(path), program.ScopeIndex.from_xplane(path))


def of_reading(reading) -> Optional[Kinds]:
    """The ``Kinds`` of the run a reader is reading: the newest trace under
    ``.bench_out/trace/`` is this run's (``program.of_reading``)."""
    if reading.trace is None:
        return None
    from benchmarks.harness import REPO

    found = glob.glob(os.path.join(REPO, ".bench_out", "trace", "*",
                                   "plugins", "profile", "*", "*.xplane.pb"))
    if not found:
        return None
    path = max(found, key=os.path.getmtime)
    return _of_file(path, os.path.getmtime(path))


# ------------------------------------------------- one call for each reader
def scope_ms_per_step(reading, name: str) -> Optional[float]:
    """Device self time a step whose innermost scope of ``SCOPES`` is
    ``name``, any phase, kernels included, ms."""
    k = of_reading(reading)
    if k is None or k.step_programs <= 0 or k.by_scope_s[name] <= 0:
        return None
    return 1e3 * k.by_scope_s[name] / k.step_programs


def kernel_roofline(reading, name: str) -> Optional[float]:
    """The windowed forward kernel's share of its roofline, percent: a third
    of the banded attention a step requires (``flops_laguna.attention_flops``:
    forward 1, backward 2) at the bf16 peak, or the pass's least bytes at the
    HBM peak, the larger, over the kernel's time.  The backward is read by
    family (``program.family_roofline``, PR 39); this walk still books the
    split kernels' time under their own names."""
    k = of_reading(reading)
    if k is None:
        return None
    f = reading.facts
    third = f.get("sliding_attention_flops_per_step")
    return program.roofline_pct(
        reading, k.by_kernel_s[name], None if third is None else third / 3,
        f.get("sliding_kernel_bytes_per_step", {}).get(_KERNEL_PART[name]),
        k.step_programs)


def gmm_held_roofline(reading) -> Optional[float]:
    """The grouped matmuls' share of their roofline, percent, over the
    routes that reached held experts as the steps counted them
    (``flops_laguna.routed_flops`` / ``grouped_matmul_bytes``): the larger
    of FLOPs over the bf16 peak and bytes over the HBM peak, over the time
    in the compiler's ``ragged-dot`` calls."""
    k = of_reading(reading)
    if k is None:
        return None
    f = reading.facts
    return program.roofline_pct(reading, k.grouped_matmul_s,
                                f.get("gmm_held_flops_per_step"),
                                f.get("gmm_held_bytes_per_step"),
                                k.step_programs)


def held_route_share(reading) -> Optional[float]:
    """Routes that reached experts held here over all routes, percent, from
    the step's own counts (no trace needed)."""
    f = reading.facts
    if not f.get("routes_per_step"):
        return None
    return 100.0 * f["held_routes_per_step"] / f["routes_per_step"]
