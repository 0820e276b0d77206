"""A traced window by the names of a model with linear-attention layers: the
scope ``attn.linear`` (inside ``attn``) of
``multiverso_tpu/models/transformer.py`` and the two passes of the chunked
scan of ``multiverso_tpu/ops/kda.py``, which run under scopes, and Pallas
calls, whose names begin ``kda_fwd`` and ``kda_bwd``.

``program.SCOPES``, ``kinds.SCOPES`` and ``latent.SCOPES`` are constants that
hold none of these names, so the readers that need them share this walk of the
run's trace (a sixth one; to be folded into ``program.py`` by a ``benchmark``
PR, ``PERF.md`` section 7).  An instruction is booked to ``attn.linear`` when
that is the innermost of the kind scopes in its ``op_name``, whatever the
phase, or when it is a pass's (the backward's inner transposes lose the outer
scopes and are named ``kda_bwd/...`` alone); and to a pass of the scan when any component of its ``op_name`` BEGINS
with that pass's name, Mosaic call or XLA fusion alike: all device time under
the scope, so that fusing or splitting the calls (``kda_bwd`` becoming one or
several kernels) cannot silence the metric.  The roofline shares are computed
from the facts the runner ``lm_train_linear`` gives
(``benchmarks/flops_ling.py``).

A program without any of this (the parent of the PR that added it, a model
without these layers) gives ``None`` and the readers leave their metric out.
"""

from __future__ import annotations

import functools
import glob
import os
from dataclasses import dataclass
from typing import Dict, Optional

from benchmarks.trace import program
from benchmarks.trace.reduce import WINDOW_SPAN, _clip, load_xplane, self_times

__all__ = ["SCOPE", "KIND_SCOPES", "PASSES", "Linear", "summarize",
           "of_reading", "scope_ms_per_step", "pass_roofline",
           "group_kept_share"]

SCOPE = "attn.linear"
KIND_SCOPES = ("attn.linear", "attn.latent", "attn.full", "attn.sliding")
PASSES = ("kda_fwd", "kda_bwd")
_PASS_PART = {"kda_fwd": "fwd", "kda_bwd": "bwd"}


@dataclass
class Linear:
    """Seconds of device self time in the window, means over the chips."""
    step_programs: int
    scope_s: float
    by_pass_s: Dict[str, float]


def _pass_of(op_name: Optional[str]) -> Optional[str]:
    for name, _ in reversed(program.components(op_name or "")):
        for which in PASSES:
            if name.startswith(which):
                return which
    return None


def summarize(trace, index) -> Optional[Linear]:
    windows = [e for e in trace.host if e.name == WINDOW_SPAN]
    if not trace.devices or not windows:
        return None
    t0 = min(w.start for w in windows)
    t1 = max(w.end for w in windows)
    chips = len(trace.devices)
    scope_ns, passes, programs = 0.0, {p: 0.0 for p in PASSES}, 0
    for dev in trace.devices.values():
        for e, self_ns in self_times(_clip(dev.ops, t0, t1)):
            op_name = index.op_name(e.name)
            which = _pass_of(op_name)
            if which is not None:
                passes[which] += self_ns
            # what the backward's inner transposes name ``kda_bwd/...`` alone
            # is the layer's all the same
            if (which is not None
                    or program.scope(op_name, among=KIND_SCOPES) == SCOPE):
                scope_ns += self_ns
        programs += sum(1 for e in _clip(dev.modules, t0, t1)
                        if e.name.startswith("jit_step"))
    if not scope_ns and not any(passes.values()):
        return None
    return Linear(step_programs=programs // chips,
                  scope_s=scope_ns / chips / 1e9,
                  by_pass_s={k: v / chips / 1e9 for k, v in passes.items()})


@functools.lru_cache(maxsize=1)
def _of_file(path: str, mtime: float) -> Optional[Linear]:
    return summarize(load_xplane(path), program.ScopeIndex.from_xplane(path))


def of_reading(reading) -> Optional[Linear]:
    """The ``Linear`` of the run a reader is reading: the newest trace under
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
def scope_ms_per_step(reading) -> Optional[float]:
    """Device self time a step under ``attn.linear``, any phase, the scan's
    two passes included, ms."""
    found = of_reading(reading)
    if found is None or found.step_programs <= 0 or found.scope_s <= 0:
        return None
    return 1e3 * found.scope_s / found.step_programs


def pass_roofline(reading, name: str) -> Optional[float]:
    """A pass of the scan's share of its roofline, percent: the recurrence's
    work as written (``flops_ling.kda_flops``) at the bf16 peak, or its least
    bytes (``kda_bytes``) at the HBM peak, the larger, over ALL device time
    under the scopes whose names begin ``name``.  A forward that remat runs
    twice counts its work once."""
    found = of_reading(reading)
    if found is None or not reading.peaks:
        return None
    part = _PASS_PART[name]
    f = reading.facts
    work = f.get("kda_flops_per_step", {}).get(part)
    moved = f.get("kda_bytes_per_step", {}).get(part)
    return program.roofline_pct(reading, found.by_pass_s[name], work, moved,
                                found.step_programs)


def group_kept_share(reading) -> Optional[float]:
    """Tokens whose kept groups include the held experts' group over all
    tokens of the routed layers, percent, from the step's own counts (no
    trace needed)."""
    f = reading.facts
    if not f.get("group_tokens_per_step"):
        return None
    return 100.0 * f["group_kept_per_step"] / f["group_tokens_per_step"]
