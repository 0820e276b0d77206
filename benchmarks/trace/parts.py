"""A traced window by the parts of a sub-layer: the scopes ``attn.proj``,
``attn.elem`` and ``attn.out`` inside ``attn`` (and the kind's own scope where
one is open), ``mlp.up`` and ``mlp.down`` inside a dense ``mlp``
(``multiverso_tpu/models/common.py:proj``, ``ops/kernel_path.py:elem``,
``models/transformer.py:_attn_sub`` / ``_mlp_sub``).

An instruction's self time is booked to the INNERMOST component of its
``op_name`` that is one of the five parts, a kernel's name (a component that
begins with one of ``KERNELS``) or ``attn.eva.summarise``; only a part's time
is kept, by ``(the kind's scope or "attn" / "mlp", part)``, so that a cell
with two kinds can be printed a kind at a time (``python -m
benchmarks.trace.parts``); the readers sum over the kinds.  XLA's grouped
matmul is never a part.  **A fusion is booked to its root's ``op_name``**, as
everywhere in ``program.py``: ``attn.proj``, ``mlp.up`` and ``mlp.down`` are
the time in fusions rooted in a product, with whatever element-wise work XLA
fused into them; ``attn.elem`` is the time in fusions that stand alone
(memory-bound passes, layout copies that keep an ``op_name``).

Beside the parts the walk keeps what would show that a scope is not closed:
of the self time whose ``op_name`` holds the component ``attn``, the part
that has no part, no kernel's name and no ``attn.eva.summarise`` among its
components, and of the time that holds ``mlp`` the part with neither
``mlp.up`` nor ``mlp.down`` (all of a routed layer's, by design).  No metric
is made of the two; the builder prints them.

One tuple of names and no facts of any runner: one more walk of the run's
trace in ``trace/linear.py``'s manner (ISSUE 50's "eighth"; the ninth file
with ``route_first.py``), written to fold into ``program.py`` (``PERF.md``
section 7).  A program without these scopes (the parent of the
PR that added them, ``zipf-b8k``) gives ``None`` and the readers leave their
metrics out.
"""

from __future__ import annotations

import functools
import glob
import json
import os
import sys
from dataclasses import dataclass
from typing import Dict, Optional, Tuple

from benchmarks.trace import program
from benchmarks.trace.reduce import (WINDOW_SPAN, _clip, is_grouped_matmul,
                                     load_xplane, self_times)

__all__ = ["PARTS", "KERNELS", "SUMMARISE", "Parts", "booked", "summarize",
           "of_reading", "part_ms_per_step"]

PARTS = ("attn.proj", "attn.elem", "attn.out", "mlp.up", "mlp.down")
SUMMARISE = "attn.eva.summarise"
# ``program.KERNELS`` lacks EVA's two (``trace/eva.py`` knows them itself)
KERNELS = program.KERNELS + ("flash_eva_fwd", "flash_eva_bwd")
ATTN, MLP = "attn", "mlp"


@dataclass
class Parts:
    """Seconds of device self time in the window, means over the chips."""
    step_programs: int
    by_part_s: Dict[Tuple[str, str], float]   # (kind's scope | attn | mlp,
                                              # part) -> seconds
    attn_s: float            # ``op_name`` holds the component ``attn``
    attn_open: Dict[str, float]   # ... and no part, kernel or summariser,
                                  # by ``op_name``
    mlp_s: float             # ``op_name`` holds the component ``mlp``
    mlp_open: Dict[str, float]    # ... and neither ``mlp.up`` nor
                                  # ``mlp.down``, by ``op_name``

    def part_s(self, part: str) -> float:
        return sum(s for (_, p), s in self.by_part_s.items() if p == part)


@functools.lru_cache(maxsize=None)       # a trace repeats a few hundred paths
def booked(op_name: Optional[str]) -> Tuple[Optional[str], Optional[str],
                                            bool, bool]:
    """``(known, under, holds attn, holds mlp)``.  ``known``: the innermost
    component that is one of ``PARTS``, ``attn.eva.summarise`` or begins with
    a kernel's name (then that name of ``KERNELS``), or None.  ``under``,
    where ``known`` is a part: the scope its time is kept under, the
    innermost ``attn.*`` component that is neither a part nor the summariser
    (the kind's), else ``attn``, or ``mlp`` for the FFN's two."""
    names = [name for name, _ in program.components(op_name or "")]
    known = under = None
    for name in reversed(names):
        known = (name if name in PARTS or name == SUMMARISE else
                 next((k for k in KERNELS if name.startswith(k)), None))
        if known is not None:
            break
    if known in PARTS:
        under = MLP if known.startswith(MLP) else next(
            (n for n in reversed(names) if n.startswith(ATTN + ".")
             and n not in PARTS and n != SUMMARISE), ATTN)
    return known, under, ATTN in names, MLP in names


def summarize(trace, index) -> Optional[Parts]:
    windows = [e for e in trace.host if e.name == WINDOW_SPAN]
    if not trace.devices or not windows:
        return None
    t0 = min(w.start for w in windows)
    t1 = max(w.end for w in windows)
    chips = len(trace.devices)
    parts: Dict[Tuple[str, str], float] = {}
    attn_open: Dict[str, float] = {}
    mlp_open: Dict[str, float] = {}
    attn = mlp = 0.0
    programs = 0
    for dev in trace.devices.values():
        for e, self_ns in self_times(_clip(dev.ops, t0, t1)):
            if is_grouped_matmul(e.name):
                continue
            op_name = index.op_name(e.name)
            known, under, in_attn, in_mlp = booked(op_name)
            if under is not None:
                key = (under, known)
                parts[key] = parts.get(key, 0.0) + self_ns
            if in_attn:
                attn += self_ns
                if known is None:
                    attn_open[op_name] = attn_open.get(op_name, 0.0) + self_ns
            if in_mlp:
                mlp += self_ns
                if under != MLP:
                    mlp_open[op_name] = mlp_open.get(op_name, 0.0) + self_ns
        programs += sum(1 for e in _clip(dev.modules, t0, t1)
                        if e.name.startswith("jit_step"))
    if not parts:
        return None

    def seconds(d):
        return {k: v / chips / 1e9 for k, v in d.items()}

    return Parts(step_programs=programs // chips, by_part_s=seconds(parts),
                 attn_s=attn / chips / 1e9, attn_open=seconds(attn_open),
                 mlp_s=mlp / chips / 1e9, mlp_open=seconds(mlp_open))


@functools.lru_cache(maxsize=1)
def _of_file(path: str, mtime: float) -> Optional[Parts]:
    return summarize(load_xplane(path), program.ScopeIndex.from_xplane(path))


def _newest_trace() -> Optional[str]:
    from benchmarks.harness import REPO

    found = glob.glob(os.path.join(REPO, ".bench_out", "trace", "*",
                                   "plugins", "profile", "*", "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


def of_reading(reading) -> Optional[Parts]:
    """The ``Parts`` of the run a reader is reading: the newest trace under
    ``.bench_out/trace/`` is this run's (``program.of_reading``)."""
    path = _newest_trace() if reading.trace is not None else None
    return _of_file(path, os.path.getmtime(path)) if path else None


def part_ms_per_step(reading, part: str) -> Optional[float]:
    """Device self time a step booked to ``part``, every kind and any phase
    together, ms: nothing where the trace holds none of the five scopes (the
    parent, ``zipf-b8k``); 0.0 where the program has them and this one holds
    no time (``mlp.up`` / ``mlp.down`` in a cell whose FFNs are all routed),
    as ``compiler.xla_remat_ms_per_step`` reads a step without clones."""
    found = of_reading(reading)
    if found is None or found.step_programs <= 0:
        return None
    return 1e3 * found.part_s(part) / found.step_programs


def main(argv) -> int:
    """``python -m benchmarks.trace.parts [trace.xplane.pb]``: the newest
    trace's split a kind (ms a step) and the two remainders, as JSON."""
    path = argv[1] if len(argv) > 1 else _newest_trace()
    found = _of_file(path, os.path.getmtime(path)) if path else None
    if found is None:
        print(json.dumps({"trace": path, "parts": None}))
        return 1
    steps = max(found.step_programs, 1)

    def ms(seconds):
        return round(1e3 * seconds / steps, 3)

    def remainder(total_s, open_s):
        left = sum(open_s.values())
        return {"ms": ms(total_s), "open_ms": ms(left),
                "open_pct": round(100 * left / max(total_s, 1e-12), 3),
                "open_most": [(name, ms(s)) for name, s in sorted(
                    open_s.items(), key=lambda kv: -kv[1])[:8]]}

    print(json.dumps({
        "trace": path, "steps": found.step_programs,
        "ms_per_step": {f"{under}/{part}": ms(s) for (under, part), s
                        in sorted(found.by_part_s.items())},
        "attn": remainder(found.attn_s, found.attn_open),
        "mlp": remainder(found.mlp_s, found.mlp_open)}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
