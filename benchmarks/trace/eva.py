"""A traced window by the names of a model with EVA attention: the scope
``attn.eva`` (inside ``attn``) of ``multiverso_tpu/models/transformer.py``, the
summariser's ``attn.eva.summarise`` inside it, and the two passes of
``multiverso_tpu/ops/flash_eva.py``, which run under scopes, and Pallas calls,
whose names begin ``flash_eva_fwd`` and ``flash_eva_bwd``.

``program.KERNELS`` and ``program.SCOPES`` are constants that hold none of
these names, so the four readers share this walk of the run's trace (in
``trace/linear.py``'s manner; to be folded into ``program.py`` by a
``benchmark`` PR, ``PERF.md`` section 7).  An instruction is booked to
``attn.eva`` when any component of its ``op_name`` is that scope or begins with
a pass's name (a backward's inner transposes may lose the outer scopes), to
``attn.eva.summarise`` when that is a component, and to a pass when any
component BEGINS with the pass's name, Mosaic call or XLA fusion alike: all
device time under the name, so that one fused call a pass, or a call a source
merged by their log-sum-exp, is read by the same number.  The roofline shares
are computed from the facts the runner ``lm_train_eva`` gives
(``benchmarks/flops_eva.py``).

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

__all__ = ["SCOPE", "SUMMARISE", "PASSES", "Eva", "summarize", "of_reading",
           "scope_ms_per_step", "pass_roofline"]

SCOPE = "attn.eva"
SUMMARISE = "attn.eva.summarise"
PASSES = ("flash_eva_fwd", "flash_eva_bwd")
_PASS_PART = {"flash_eva_fwd": "fwd", "flash_eva_bwd": "bwd"}


@dataclass
class Eva:
    """Seconds of device self time in the window, means over the chips."""
    step_programs: int
    by_scope_s: Dict[str, float]
    by_pass_s: Dict[str, float]


def _booked(op_name: Optional[str]):
    """``(under attn.eva, under attn.eva.summarise, the pass or None)``."""
    names = [name for name, _ in program.components(op_name or "")]
    which = next((p for name in reversed(names) for p in PASSES
                  if name.startswith(p)), None)
    summarise = SUMMARISE in names
    return (summarise or which is not None or SCOPE in names, summarise,
            which)


def summarize(trace, index) -> Optional[Eva]:
    windows = [e for e in trace.host if e.name == WINDOW_SPAN]
    if not trace.devices or not windows:
        return None
    t0 = min(w.start for w in windows)
    t1 = max(w.end for w in windows)
    chips = len(trace.devices)
    scopes = {SCOPE: 0.0, SUMMARISE: 0.0}
    passes, programs = {p: 0.0 for p in PASSES}, 0
    for dev in trace.devices.values():
        for e, self_ns in self_times(_clip(dev.ops, t0, t1)):
            inside, summarise, which = _booked(index.op_name(e.name))
            if inside:
                scopes[SCOPE] += self_ns
            if summarise:
                scopes[SUMMARISE] += self_ns
            if which is not None:
                passes[which] += self_ns
        programs += sum(1 for e in _clip(dev.modules, t0, t1)
                        if e.name.startswith("jit_step"))
    if not scopes[SCOPE]:
        return None
    return Eva(step_programs=programs // chips,
               by_scope_s={k: v / chips / 1e9 for k, v in scopes.items()},
               by_pass_s={k: v / chips / 1e9 for k, v in passes.items()})


@functools.lru_cache(maxsize=1)
def _of_file(path: str, mtime: float) -> Optional[Eva]:
    return summarize(load_xplane(path), program.ScopeIndex.from_xplane(path))


def of_reading(reading) -> Optional[Eva]:
    """The ``Eva`` of the run a reader is reading: the newest trace under
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
def scope_ms_per_step(reading, scope: str = SCOPE) -> Optional[float]:
    """Device self time a step under ``scope`` (``attn.eva``: the four
    projections, rotary, the summariser and both passes of the attention;
    ``attn.eva.summarise``: the pooling alone), any phase, ms."""
    found = of_reading(reading)
    if (found is None or found.step_programs <= 0
            or found.by_scope_s[scope] <= 0):
        return None
    return 1e3 * found.by_scope_s[scope] / found.step_programs


def pass_roofline(reading, name: str) -> Optional[float]:
    """A pass of the attention's share of its roofline, percent: what the pass
    requires (``flops_eva.eva_flops``: exact pairs) at the bf16 peak, or its
    least bytes (``eva_bytes``) at the HBM peak, the larger, over ALL device
    time under names that begin ``name``.  A forward that remat runs twice
    counts its work once."""
    found = of_reading(reading)
    if found is None or not reading.peaks:
        return None
    part = _PASS_PART[name]
    f = reading.facts
    work = f.get("eva_flops_per_step", {}).get(part)
    moved = f.get("eva_bytes_per_step", {}).get(part)
    return program.roofline_pct(reading, found.by_pass_s[name], work, moved,
                                found.step_programs)
