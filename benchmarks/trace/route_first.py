"""A traced window by the names of a model whose router chooses before
attention runs: the scopes ``route_early`` (the block's own, outside
``attn``: ``multiverso_tpu/models/transformer.py:_make_block``),
``attn.full_nope`` and ``attn.sliding`` (inside ``attn``:
``models/attention/softmax.py``).

``program.SCOPES`` is a constant that does not hold these names, so the
three scope readers share this walk of the run's trace (a fifth one; to be
folded into ``program.py`` by a ``benchmark`` PR, ``PERF.md`` section 7).  An
instruction is booked to the innermost of ``SCOPES`` in its ``op_name``,
whatever the phase.  The kernels' rooflines need no walk of their own:
``program.KERNELS`` holds the four flash families and XLA's grouped matmul
(``program.family_roofline``), and the work and bytes come from the facts the
runner ``lm_train_route_first`` gives (``benchmarks/flops_smallthinker.py``).

A program without any of these scopes (the parent of the PR that added them)
gives ``None`` and the readers leave their metric out.
"""

from __future__ import annotations

import functools
import glob
import os
from dataclasses import dataclass
from typing import Dict, Optional

from benchmarks.trace import program
from benchmarks.trace.reduce import (WINDOW_SPAN, _clip, load_xplane,
                                     self_times)

__all__ = ["SCOPES", "Scoped", "summarize", "of_reading",
           "scope_ms_per_step", "pass_roofline"]

SCOPES = ("route_early", "attn.full_nope", "attn.sliding")


@dataclass
class Scoped:
    """Seconds of device self time in the window, means over the chips."""
    step_programs: int
    by_scope_s: Dict[str, float]


def summarize(trace, index) -> Optional[Scoped]:
    windows = [e for e in trace.host if e.name == WINDOW_SPAN]
    if not trace.devices or not windows:
        return None
    t0 = min(w.start for w in windows)
    t1 = max(w.end for w in windows)
    chips = len(trace.devices)
    scopes = {s: 0.0 for s in SCOPES}
    programs = 0
    for dev in trace.devices.values():
        for e, self_ns in self_times(_clip(dev.ops, t0, t1)):
            where = program.scope(index.op_name(e.name), among=SCOPES)
            if where is not None:
                scopes[where] += self_ns
        programs += sum(1 for e in _clip(dev.modules, t0, t1)
                        if e.name.startswith("jit_step"))
    if not any(scopes.values()):
        return None
    return Scoped(step_programs=programs // chips,
                  by_scope_s={k: v / chips / 1e9 for k, v in scopes.items()})


@functools.lru_cache(maxsize=1)
def _of_file(path: str, mtime: float) -> Optional[Scoped]:
    return summarize(load_xplane(path), program.ScopeIndex.from_xplane(path))


def of_reading(reading) -> Optional[Scoped]:
    """The ``Scoped`` of the run a reader is reading: the newest trace under
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
    found = of_reading(reading)
    if found is None or found.step_programs <= 0 or found.by_scope_s[
            name] <= 0:
        return None
    return 1e3 * found.by_scope_s[name] / found.step_programs


def pass_roofline(reading, family: str, fact: str) -> Optional[float]:
    """The share of its roofline, percent, of everything the device runs
    under names that begin ``family`` (``program.family_roofline``), by the
    runner's fact ``fact``: ``{"flops", "bytes"}`` a step, what the pass
    requires; nothing where the runner gave none."""
    need = reading.facts.get(fact)
    if not need:
        return None
    return program.family_roofline(reading, family, need["flops"],
                                   need["bytes"])
