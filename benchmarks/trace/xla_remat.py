"""A traced window by what XLA's own rematerialisation pass added to the step.

Near the memory limit the pass clones an instruction beside a later use and
frees the first result, so the value is computed twice (``PERF.md``, PR 33: a
``conditional`` in the step sets it off).  It names a clone after what it
copies, with a suffix: ``fusion.6830.remat``, ``fusion.6830.remat2``,
``dot.12.remat.1``.  JAX's ``jax.checkpoint`` recompute is another thing: it
lives in ``op_name`` paths as ``rematted_computation`` and is never an
instruction's name, and ``model.remat_ms_per_step`` reads it.  A clone keeps
the ``op_name`` of its original, so its time is *also* in the phases' and the
scopes' metrics: this walk is a cut across them.

``program.py`` keeps no instruction names, so this is a walk of the run's
trace of its own (a sixth one; to be folded with the others by a ``benchmark``
PR, ``PERF.md`` section 7).  A window with a step program and no clone reads
0.0; no device plane, window or step program reads ``None``.

``python -m benchmarks.trace.xla_remat <trace.xplane.pb>`` prints the clones
of a trace with their time a step and their ``op_name``, most first.
"""

from __future__ import annotations

import functools
import glob
import os
import re
import sys
from dataclasses import dataclass
from typing import List, Optional, Tuple

from benchmarks.trace.reduce import (WINDOW_SPAN, _clip, instruction_name,
                                     load_xplane, self_times)

__all__ = ["CLONE", "Clones", "is_clone", "summarize", "of_reading",
           "ms_per_step"]

# a name component ``remat`` or ``remat<n>``, as ``HloInstruction::Clone``
# writes the suffix; ``rematted_computation`` is not one
CLONE = re.compile(r"\.remat\d*(?:\.|$)")


def is_clone(event_name: str) -> bool:
    """Whether a device event is one of the pass's clones, by the name of
    its instruction alone (never the text right of the ``=``, which may call
    a computation of any name)."""
    return CLONE.search(instruction_name(event_name)) is not None


@dataclass
class Clones:
    """Seconds of device self time in the window, means over the chips."""
    step_programs: int
    busy_s: float
    clones_s: float
    by_instruction_s: List[Tuple[str, float]]      # most first


def summarize(trace) -> Optional[Clones]:
    windows = [e for e in trace.host if e.name == WINDOW_SPAN]
    if not trace.devices or not windows:
        return None
    t0 = min(w.start for w in windows)
    t1 = max(w.end for w in windows)
    chips = len(trace.devices)
    busy = 0.0
    programs = 0
    clones = {}
    for dev in trace.devices.values():
        for e, self_ns in self_times(_clip(dev.ops, t0, t1)):
            busy += self_ns
            if is_clone(e.name):
                name = instruction_name(e.name)
                clones[name] = clones.get(name, 0.0) + self_ns
        programs += sum(1 for e in _clip(dev.modules, t0, t1)
                        if e.name.startswith("jit_step"))
    if programs < chips:
        return None
    return Clones(step_programs=programs // chips,
                  busy_s=busy / chips / 1e9,
                  clones_s=sum(clones.values()) / chips / 1e9,
                  by_instruction_s=sorted(
                      ((k, v / chips / 1e9) for k, v in clones.items()),
                      key=lambda kv: -kv[1]))


@functools.lru_cache(maxsize=1)
def _of_file(path: str, mtime: float) -> Optional[Clones]:
    return summarize(load_xplane(path))


def of_reading(reading) -> Optional[Clones]:
    """The ``Clones`` of the run a reader is reading: the newest trace under
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


def ms_per_step(reading) -> Optional[float]:
    """Device self time a step in the pass's clones, ms; 0.0 where the step
    holds none."""
    found = of_reading(reading)
    if found is None:
        return None
    return 1e3 * found.clones_s / found.step_programs


if __name__ == "__main__":
    from benchmarks.trace import program

    found = summarize(load_xplane(sys.argv[1]))
    if found is None:
        sys.exit("no device plane, window or step program in the trace")
    print(f"{found.step_programs} step programs, "
          f"{1e3 * found.busy_s / found.step_programs:.3f} ms busy a step, "
          f"{1e3 * found.clones_s / found.step_programs:.3f} ms in "
          f"{len(found.by_instruction_s)} clones")
    op_names = program.ScopeIndex.from_xplane(sys.argv[1]).op_names
    for name, seconds in found.by_instruction_s:
        print(f"{1e3 * seconds / found.step_programs:10.3f} ms  {name}  "
              f"{op_names.get(name, '')}")
