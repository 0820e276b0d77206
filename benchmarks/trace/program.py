"""A traced window through the program's own names: the scopes in the
compiled steps, the names of the flash kernels and the ``mv.*`` host spans
(``PERF.md`` section 3 lists them with their files), and XLA's grouped
matmul by the compiler's own name for it.

What the compiled program says about an instruction is its ``op_name``, a
path that JAX writes from the name stack at trace time::

    jit(step)/jvp(layers)/while/body/closed_call/attn/flash_fwd/flash_fwd/pallas_call
    jit(step)/transpose(jvp(layers))/while/body/closed_call/checkpoint/rematted_computation/attn/mul
    jit(step)/update/sub
    jit(step)/tables.scatter_apply/scatter-add

A component is a scope, bare or wrapped in the transformations it went
through (``transpose(jvp(layers))``), or a primitive (``mul``).  ``phase``
and ``scope`` compare whole components, never substrings
(``dynamic_update_slice`` is not ``update``).  The ``op_name`` of an
instruction comes from ``compiled.as_text()`` (``ScopeIndex(texts)``) or
from the trace itself (``ScopeIndex.from_xplane``: the profiler stores it
with every device event's metadata as the stat ``tf_op``, which
``jax.profiler.ProfileData`` does not show, so the file's protobuf is walked
here).  A fusion is booked to its own ``op_name``, which is its root's: the
approximation every number below rests on.  Instructions the compiler adds
(layout copies, prefetches into fast memory) have no ``op_name``, or only a
parameter's name, and count as unscoped.

Pure functions over ``Event`` lists and text, like ``reduce.py``'s; the one
exception is ``of_reading``, which finds the trace the harness wrote.
"""

from __future__ import annotations

import functools
import glob
import os
import re
from dataclasses import dataclass
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from benchmarks import flops
from benchmarks.trace.reduce import (_INSTRUCTION, GROUPED_MATMUL,
                                     WINDOW_SPAN, Event, Trace, _clip,
                                     classify, instruction_name,
                                     is_grouped_matmul, load_xplane,
                                     self_times)

__all__ = ["SCOPES", "KERNELS", "PHASES", "SPAN_PREFIX", "ScopeIndex",
           "components", "phase", "scope", "unscoped", "kernel",
           "span_totals", "Program", "summarize", "of_reading",
           "phase_ms_per_step", "scope_ms_per_step", "kernel_roofline",
           "family_roofline", "roofline_pct", "unscoped_share", "span_ms"]

# A kernel is known by what its calls' names BEGIN with: ``flash_bwd`` is the
# fused call where there is one and ``flash_bwd_dq`` + ``flash_bwd_dkv`` where
# the program splits it (``ops/flash_attention.py:_fused_fits``), so a metric
# reads the same work whatever implements it.  No name here begins another.
KERNELS = ("flash_fwd", "flash_bwd", "flash_win_fwd", "flash_win_bwd",
           "flash_mla_fwd", "flash_mla_bwd", "kda_fwd", "kda_bwd",
           "row_update")
# The scopes device time is booked to (the innermost counts).  The attention
# and scan kernels are scopes of their own inside ``attn``; ``row_update`` is
# not: ``tables.scatter_apply`` is all a step pays to apply its rows, the
# kernel included (``tables.scatter_apply_ms_per_step``).
SCOPES = ("embed", "layers", "attn", "mlp", "head", "loss", "update",
          "tables.gather", "sgns.grad", "tables.scatter_apply") + tuple(
              k for k in KERNELS if k != "row_update")
PHASES = ("fwd", "bwd", "remat", "update", "other")
SPAN_PREFIX = "mv."
REMAT = "rematted_computation"

_WRAPPED = re.compile(r"^((?:[\w.\-]+\()*)([^()]*)\)*$")


@functools.lru_cache(maxsize=None)       # a trace repeats a few hundred paths
def components(op_name: str) -> Tuple[Tuple[str, Tuple[str, ...]], ...]:
    """``transpose(jvp(layers))/while`` -> ``(("layers", ("transpose",
    "jvp")), ("while", ()))``: each component's innermost name and the
    transformations around it, outermost first."""
    out = []
    for part in op_name.split("/"):
        m = _WRAPPED.match(part)
        if m:
            out.append((m.group(2), tuple(m.group(1).split("(")[:-1])))
        else:
            out.append((part, ()))
    return tuple(out)


def phase(op_name: Optional[str]) -> str:
    """``remat`` if recomputed, else ``update`` if under that scope, else
    ``bwd`` if transposed, else ``fwd`` if differentiated, else ``other``."""
    parts = components(op_name or "")
    if any(name == REMAT for name, _ in parts):
        return "remat"
    if any(name == "update" for name, _ in parts):
        return "update"
    if any("transpose" in wraps for _, wraps in parts):
        return "bwd"
    if any("jvp" in wraps for _, wraps in parts):
        return "fwd"
    return "other"


def _kernel_of(component: str) -> Optional[str]:
    return next((k for k in KERNELS if component.startswith(k)), None)


@functools.lru_cache(maxsize=None)       # a trace repeats a few hundred paths
def scope(op_name: Optional[str], among: Sequence[str] = SCOPES
          ) -> Optional[str]:
    """The innermost of the program's scope names in the path, or none.  A
    component that begins with a kernel's name counts as that kernel's scope
    where ``among`` (a tuple) holds it (``flash_bwd_dq`` is ``flash_bwd``'s)."""
    for name, _ in reversed(components(op_name or "")):
        if name in among:
            return name
        family = _kernel_of(name)
        if family in among:
            return family
    return None


def kernel(op_name: Optional[str]) -> Optional[str]:
    """Which of ``KERNELS`` a device event runs under: the innermost
    component of its ``op_name`` that begins with a kernel's name, the
    Mosaic call and any fusion the scope holds alike (all device time under
    the name, as ``trace/linear.py`` reads the scan's passes).  The v5e
    program also names the call's instruction after it, ``flash_bwd.10``;
    the ``op_name`` is what this goes by."""
    return scope(op_name, KERNELS)


def unscoped(op_name: Optional[str]) -> bool:
    """No ``op_name``, or one that holds neither a phase nor a scope."""
    return not op_name or (phase(op_name) == "other"
                           and scope(op_name) is None)


# ------------------------------------------------------- where op_names are
_OP_NAME = re.compile(r'op_name="([^"]*)"')


class ScopeIndex:
    """Instruction name -> ``op_name``, from the compiled programs' text."""

    def __init__(self, hlo_texts: Iterable[str] = ()):
        self.op_names: Dict[str, str] = {}
        for text in hlo_texts:
            for raw in text.splitlines():
                ins = _INSTRUCTION.match(raw)
                found = _OP_NAME.search(raw) if ins else None
                if found:
                    self.op_names.setdefault(ins.group(1), found.group(1))

    def op_name(self, event_name: str) -> Optional[str]:
        """By the event's whole text where the trace gave it (two programs
        in one trace may both hold a ``fusion.1``), else by its name."""
        return (self.op_names.get(event_name)
                or self.op_names.get(instruction_name(event_name)))

    @classmethod
    def from_xplane(cls, path: str) -> "ScopeIndex":
        """The mapping read off a trace: every event metadata of a device
        plane that carries the stat ``tf_op`` (``<op_name>:<type>``).  The
        profiler lends an instruction without metadata of its own (a layout
        copy inside the backward scan) the ``op_name`` of the ``while`` that
        runs it, which the text does not, and gives a ``while`` none."""
        index = cls()
        with open(path, "rb") as f:
            space = memoryview(f.read())
        for plane in _sub(space, 1):                       # XSpace.planes
            if not bytes(_first(plane, 2) or b"").startswith(b"/device:"):
                continue                                   # XPlane.name
            tf_op = {_first(entry, 1)                      # .stat_metadata
                     for entry in _sub(plane, 5)
                     if bytes(_first(_first(entry, 2), 2) or b"") == b"tf_op"}
            for entry in _sub(plane, 4):                   # .event_metadata
                meta = _first(entry, 2)
                for stat in _sub(meta, 5):                 # XEventMetadata.stats
                    if _first(stat, 1) not in tf_op:
                        continue
                    value = bytes(_first(stat, 5) or b"")  # XStat.str_value
                    op_name = value.decode().rsplit(":", 1)[0]
                    name = bytes(_first(meta, 2) or b"").decode()
                    if op_name:
                        index.op_names[name] = op_name
                        index.op_names.setdefault(instruction_name(name),
                                                  op_name)
        return index


def _varint(buf, at: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[at]
        at += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, at
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: an int for a varint,
    a memoryview for a length-delimited or fixed-width field."""
    at, end = 0, len(buf)
    while at < end:
        key, at = _varint(buf, at)
        wire = key & 7
        if wire == 0:
            value, at = _varint(buf, at)
        else:
            if wire == 2:
                size, at = _varint(buf, at)
            elif wire in (1, 5):
                size = 8 if wire == 1 else 4
            else:
                raise ValueError(f"protobuf wire type {wire}")
            value = buf[at:at + size]
            at += size
        yield key >> 3, value


def _sub(buf, number: int):
    return [v for n, v in _fields(buf) if n == number]


def _first(buf, number: int):
    for n, v in _fields(buf):
        if n == number:
            return v
    return None


# ----------------------------------------------------------------- summary
def span_totals(host: Iterable[Event], t0: float,
                t1: float) -> Dict[str, Tuple[float, int]]:
    """Seconds inside ``[t0, t1]`` and count of each of the program's host
    spans (``mv.*``); one that straddles an edge counts its inner part."""
    out: Dict[str, Tuple[float, int]] = {}
    for e in _clip([e for e in host if e.name.startswith(SPAN_PREFIX)],
                   t0, t1):
        total, count = out.get(e.name, (0.0, 0))
        out[e.name] = (total + e.dur / 1e9, count + 1)
    return out


@dataclass
class Program:
    """One traced window by the program's names.  Seconds; device numbers
    are means over the chips in the trace, self time (a ``while`` does not
    count its body), so the phases add up to ``busy_s``."""
    step_programs: int
    busy_s: float
    by_phase_s: Dict[str, float]
    by_scope_s: Dict[str, float]              # innermost scope, any phase
    by_kernel_s: Dict[str, float]             # all time under ``KERNELS``'
                                              # names; XLA's grouped matmul
                                              # under ``ragged-dot``
    by_call_s: Dict[str, float]               # ... in the Mosaic calls alone
    unscoped_s: List[Tuple[str, float]]       # by instruction, most first
    spans: Dict[str, Tuple[float, int]]       # mv.* -> (seconds, count)


def summarize(trace: Trace, index: ScopeIndex) -> Optional[Program]:
    """Reduce the window marked by ``bench.window``; ``None`` when the trace
    holds no device plane or no window.  A step program is an execution of
    ``jit_step``, as in ``reduce.summarize``."""
    windows = [e for e in trace.host if e.name == WINDOW_SPAN]
    if not trace.devices or not windows:
        return None
    t0 = min(w.start for w in windows)
    t1 = max(w.end for w in windows)
    chips = len(trace.devices)
    phases = {p: 0.0 for p in PHASES}
    scopes: Dict[str, float] = {}
    kernels: Dict[str, float] = {}
    calls: Dict[str, float] = {}
    loose: Dict[str, float] = {}
    programs = 0
    for dev in trace.devices.values():
        for e, self_ns in self_times(_clip(dev.ops, t0, t1)):
            op_name = index.op_name(e.name)
            phases[phase(op_name)] += self_ns
            # XLA's grouped matmul drops the program's scope and phase: it
            # is known by its name, and is not what the names cannot see
            grouped = is_grouped_matmul(e.name)
            family = GROUPED_MATMUL if grouped else kernel(op_name)
            for key, into in ((scope(op_name), scopes), (family, kernels),
                              (family if classify(e.name) == "mosaic"
                               else None, calls)):
                if key is not None:
                    into[key] = into.get(key, 0.0) + self_ns
            if unscoped(op_name) and not grouped:
                name = instruction_name(e.name)
                loose[name] = loose.get(name, 0.0) + self_ns
        programs += sum(1 for e in _clip(dev.modules, t0, t1)
                        if e.name.startswith("jit_step"))

    def seconds(d):
        return {k: v / chips / 1e9 for k, v in d.items()}

    return Program(
        step_programs=programs // chips,
        busy_s=sum(phases.values()) / chips / 1e9,
        by_phase_s=seconds(phases), by_scope_s=seconds(scopes),
        by_kernel_s=seconds(kernels), by_call_s=seconds(calls),
        unscoped_s=sorted(seconds(loose).items(), key=lambda kv: -kv[1]),
        spans=span_totals(trace.host, t0, t1))


# ------------------------------------------------- what a reader is handed
@functools.lru_cache(maxsize=1)
def _of_file(path: str, mtime: float) -> Optional[Program]:
    return summarize(load_xplane(path), ScopeIndex.from_xplane(path))


def of_reading(reading) -> Optional[Program]:
    """The ``Program`` of the run a reader is reading, or ``None`` off the
    chip.  ``Reading`` carries neither the host line nor the program's text
    (``PERF.md`` section 7), so the trace the harness has just written under
    ``.bench_out/trace/<cell>/`` is read again: the newest one is this
    run's, since each run removes its cell's before it starts."""
    if reading.trace is None:
        return None
    from benchmarks.harness import REPO

    found = glob.glob(os.path.join(REPO, ".bench_out", "trace", "*",
                                   "plugins", "profile", "*", "*.xplane.pb"))
    if not found:
        return None
    path = max(found, key=os.path.getmtime)
    return _of_file(path, os.path.getmtime(path))      # once for all readers


# The readers under ``layer_metrics/`` are one call each to these; every one
# gives ``None`` where there is nothing to read: off the chip, or a program
# without the scope or span (the parent of the PR that added them).
def _ms_per_step(prog: Optional[Program], seconds: float) -> Optional[float]:
    if prog is None or prog.step_programs <= 0 or seconds <= 0:
        return None
    return 1e3 * seconds / prog.step_programs


def phase_ms_per_step(reading, name: str) -> Optional[float]:
    """Device self time in phase ``name`` per executed step program, ms."""
    prog = of_reading(reading)
    return _ms_per_step(prog, prog.by_phase_s[name]) if prog else None


def scope_ms_per_step(reading, *names: str) -> Optional[float]:
    """Device self time whose innermost scope is one of ``names``, any
    phase, per executed step program, ms."""
    prog = of_reading(reading)
    return _ms_per_step(prog, sum(prog.by_scope_s.get(n, 0.0)
                                  for n in names)) if prog else None


def kernel_roofline(reading, name: str) -> Optional[float]:
    """Kernel ``name``'s share of its compute roofline, percent: a third of
    the causal attention a step requires (``flops.causal_attention_flops``:
    forward 1, backward 2 = dP + dQ and dV + dK; the scores a backward
    kernel rebuilds are recompute) at the bf16 peak, over the time in the
    Mosaic calls of that name alone (what XLA books to the kernel's name
    beside them, a layout copy of its result, is not counted here: the
    forward readers of PR 23 stand as they read)."""
    prog = of_reading(reading)
    work = reading.facts.get("attention_flops_per_step")
    if prog is None:
        return None
    return roofline_pct(reading, prog.by_call_s.get(name, 0.0),
                        None if work is None else work / 3, 0.0,
                        prog.step_programs)


def family_roofline(reading, name: str, work: Optional[float],
                    moved: Optional[float]) -> Optional[float]:
    """The share of its roofline, percent, of everything the device runs
    under names that begin ``name`` (one of ``KERNELS``): ``work`` FLOPs a
    step at the bf16 peak or ``moved`` bytes a step at the HBM peak, the
    larger, over ALL that device time.  ``work`` and ``moved`` are what the
    algorithm requires, so a program that fuses two calls into one, or
    splits one, is read by the same number, and one that issues less than it
    requires cannot read over 100%.  Nothing where the trace holds no such
    call, or the runner gave no count."""
    prog = of_reading(reading)
    if prog is None:
        return None
    return roofline_pct(reading, prog.by_kernel_s.get(name, 0.0), work, moved,
                        prog.step_programs)


def roofline_pct(reading, spent_s: float, work: Optional[float],
                 moved: Optional[float], step_programs: int
                 ) -> Optional[float]:
    """``work`` FLOPs and ``moved`` bytes a step, over ``step_programs``
    steps on the cell's chips, at the peaks (the larger of the two times)
    over ``spent_s`` seconds of device time, percent; nothing where there is
    no time, no peak or no count."""
    if spent_s <= 0 or not reading.peaks or work is None or moved is None:
        return None
    per_chip = step_programs / reading.facts["chips"]
    least_s, _bound = flops.roofline_seconds(work * per_chip,
                                             moved * per_chip, reading.peaks)
    return 100.0 * least_s / spent_s


def unscoped_share(reading) -> Optional[float]:
    """Share of device busy time in unscoped instructions, percent; nothing
    where the program holds no scope at all."""
    prog = of_reading(reading)
    if prog is None or not prog.by_scope_s or prog.busy_s <= 0:
        return None
    return 100.0 * sum(s for _, s in prog.unscoped_s) / prog.busy_s


def span_ms(reading, name: str, per: Optional[str] = None) -> Optional[float]:
    """Host time under span ``name`` inside the window over the number of
    those spans (or of spans ``per``), ms."""
    prog = of_reading(reading)
    if prog is None:
        return None
    total, count = prog.spans.get(name, (0.0, 0))
    if per is not None:
        count = prog.spans.get(per, (0.0, 0))[1]
    return 1e3 * total / count if count and total > 0 else None
