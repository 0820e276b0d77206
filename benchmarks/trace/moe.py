"""A traced window by the routed FFN's own names: the four ``moe.*`` scopes
of ``multiverso_tpu/models/moe.py`` and XLA's grouped-matmul calls.

``program.SCOPES`` does not hold these names, so the four MoE readers under
``layer_metrics/`` share this walk of the run's trace (a third one; to be
folded into ``program.py`` by a later ``benchmark`` PR, ``PERF.md`` section
7).  Two rules book an instruction to a scope:

- its ``op_name`` holds one of ``SCOPES`` (the innermost counts), whatever
  the phase: ``.../mlp/moe.dispatch/sort``;
- or it is the compiler's grouped matmul.  XLA:TPU rewrites a
  ``jax.lax.ragged_dot`` into ``tpu_custom_call``s of its own and names them
  (instruction and ``op_name`` alike) ``ragged-dot-none.N``, with a small
  ``ragged-dot-metadata.N`` call before each group of them; the rewrite
  drops the program's scope and phase (seen in the v5e compile, PR 26), so
  they are known by that name (``reduce.is_grouped_matmul``) and booked to
  ``moe.experts``.

A program without any of this (the parent of the PR that added it, a dense
model) gives ``None`` and the readers leave their metric out.
"""

from __future__ import annotations

import functools
import glob
import os
from dataclasses import dataclass
from typing import Dict, Optional

from benchmarks import flops, flops_moe
from benchmarks.trace import program
from benchmarks.trace.reduce import (GROUPED_MATMUL, WINDOW_SPAN, _clip,
                                     is_grouped_matmul, load_xplane,
                                     self_times)

__all__ = ["SCOPES", "GROUPED_MATMUL", "MoE", "book", "summarize",
           "of_reading", "share", "scope_ms_per_step", "gmm_roofline"]

SCOPES = ("moe.route", "moe.dispatch", "moe.experts", "moe.combine")
EXPERTS = "moe.experts"


def book(event_name: str, op_name: Optional[str]) -> Optional[str]:
    """The ``moe.*`` scope a device event belongs to, or none."""
    found = program.scope(op_name, among=SCOPES)
    if found is None and is_grouped_matmul(event_name):
        return EXPERTS
    return found


@dataclass
class MoE:
    """Seconds of device self time in the window, means over the chips."""
    cell: str
    step_programs: int
    busy_s: float
    by_scope_s: Dict[str, float]
    grouped_matmul_s: float        # the custom calls under ``moe.experts``


def summarize(trace, index, cell: str = "") -> Optional[MoE]:
    windows = [e for e in trace.host if e.name == WINDOW_SPAN]
    if not trace.devices or not windows:
        return None
    t0 = min(w.start for w in windows)
    t1 = max(w.end for w in windows)
    chips = len(trace.devices)
    scopes = {s: 0.0 for s in SCOPES}
    busy = calls = 0.0
    programs = 0
    for dev in trace.devices.values():
        for e, self_ns in self_times(_clip(dev.ops, t0, t1)):
            busy += self_ns
            where = book(e.name, index.op_name(e.name))
            if where is not None:
                scopes[where] += self_ns
                if is_grouped_matmul(e.name):
                    calls += self_ns
        programs += sum(1 for e in _clip(dev.modules, t0, t1)
                        if e.name.startswith("jit_step"))
    if not any(scopes.values()):
        return None
    return MoE(cell=cell, step_programs=programs // chips,
               busy_s=busy / chips / 1e9,
               by_scope_s={k: v / chips / 1e9 for k, v in scopes.items()},
               grouped_matmul_s=calls / chips / 1e9)


@functools.lru_cache(maxsize=1)
def _of_file(path: str, mtime: float, cell: str) -> Optional[MoE]:
    return summarize(load_xplane(path),
                     program.ScopeIndex.from_xplane(path), cell)


def of_reading(reading) -> Optional[MoE]:
    """The ``MoE`` of the run a reader is reading: the newest trace under
    ``.bench_out/trace/<cell>/`` is this run's (``program.of_reading``), and
    the directory's name is the cell's."""
    if reading.trace is None:
        return None
    from benchmarks.harness import REPO

    root = os.path.join(REPO, ".bench_out", "trace")
    found = glob.glob(os.path.join(root, "*", "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not found:
        return None
    path = max(found, key=os.path.getmtime)
    cell = os.path.relpath(path, root).split(os.sep)[0]
    return _of_file(path, os.path.getmtime(path), cell)


# ------------------------------------------------- one call for each reader
def share(reading) -> Optional[float]:
    """Percent of device busy time under the four scopes, any phase."""
    moe = of_reading(reading)
    if moe is None or moe.busy_s <= 0:
        return None
    return 100.0 * sum(moe.by_scope_s.values()) / moe.busy_s


def scope_ms_per_step(reading, *names: str) -> Optional[float]:
    moe = of_reading(reading)
    if moe is None or moe.step_programs <= 0:
        return None
    return 1e3 * sum(moe.by_scope_s[n] for n in names) / moe.step_programs


def gmm_roofline(reading) -> Optional[float]:
    """The grouped matmuls' share of their roofline, percent: the least
    time the chip could take for the nine grouped matmuls a layer a step
    requires (``flops_moe``: the larger of FLOPs over the bf16 peak and
    bytes over the HBM peak; a forward matmul run again under remat is
    recompute and counts nothing) over the time in the compiler's
    grouped-matmul calls."""
    moe = of_reading(reading)
    if (moe is None or moe.grouped_matmul_s <= 0 or not reading.peaks
            or not moe.cell):
        return None
    from benchmarks.harness import load_cell

    model = load_cell(moe.cell).config["model"]
    tokens = reading.facts["tokens_per_step"]
    per_chip = moe.step_programs / reading.facts["chips"]
    least_s, _bound = flops.roofline_seconds(
        flops_moe.grouped_matmul_flops(model, tokens) * per_chip,
        flops_moe.grouped_matmul_bytes(model, tokens) * per_chip,
        reading.peaks)
    return 100.0 * least_s / moe.grouped_matmul_s
