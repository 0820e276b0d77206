"""From a profiler trace (``.xplane.pb``) to the numbers the benchmark prints.

What a trace of this system on a TPU v5e looks like (read by hand, PR 22,
``benchmarks/trace/record_fixture.py``):

- one plane ``/device:TPU:<n>`` per chip, with the lines ``XLA Ops`` (what
  the core executes; a ``while`` event spans its body's events, so events
  nest), ``Async XLA Ops`` (DMA in flight: ``copy-start``, ``slice-start``,
  async collectives, from start to done), ``XLA Modules`` (one event per
  program execution) and ``Steps``;
- an event's name on those lines is the whole HLO instruction
  (``%fusion.4 = f32[...] fusion(...), kind=kCustom, calls=%fused_computation.8``);
  no event carries an HLO category, so the category is worked out here from
  the instruction's opcode and, for a fusion, from the opcodes of the
  computation it calls, looked up in the compiled program's text;
- a Mosaic kernel is a ``custom-call`` with
  ``custom_call_target="tpu_custom_call"``.  Since PR 23 the program names
  its ``pallas_call``s and the v5e compiler names the instruction after the
  kernel (``flash_bwd.10``, ``kda_fwd.26``, ``row_update.3``); XLA's own
  grouped matmul is such a call too (``ragged-dot-none.N``).  ``classify``
  books the two that are not attention kernels where their work belongs
  (``_CALLS_BY_NAME``); an unnamed call (``closed_call.6``) stays ``mosaic``;
- ``jax.profiler.TraceAnnotation`` spans land on the ``python`` line of the
  plane ``/host:CPU``, on the same clock as the device events (nanoseconds
  since the profile began), beside JAX's own host events
  (``PjitFunction(step)``, ``DevicePutWithSharding``, ...).

Everything below the loader works on plain ``Event`` lists, so the arithmetic
is tested on hand-made intervals as well as on the recorded fixture.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

__all__ = ["Event", "DeviceLines", "Trace", "Summary", "load_xplane",
           "HloIndex", "classify", "instruction_name", "union", "covered_ns",
           "subtract", "self_times", "summarize", "breakdown",
           "is_grouped_matmul", "WINDOW_SPAN", "CATEGORIES", "GROUPED_MATMUL"]

# The runner wraps its measuring loop in this span; device events are
# clipped to it, so profiler start-up and shut-down are not read as idle.
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."

CATEGORIES = ("mosaic", "matmul", "collective", "scatter_gather", "copy",
              "elementwise", "control", "other")

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
                "collective-permute", "all-to-all", "collective-broadcast")
_MOVES = {"copy", "transpose", "bitcast", "reshape", "slice", "pad",
          "concatenate", "copy-start", "copy-done", "slice-start",
          "slice-done", "dynamic-slice", "broadcast", "parameter", "tuple",
          "get-tuple-element", "constant", "convert", "iota"}
_CONTROL = {"while", "conditional", "call"}
# ``tpu_custom_call``s that are no attention or scan kernel, by what the
# instruction's name begins with: XLA's grouped matmul (the rewrite of
# ``jax.lax.ragged_dot``; ``ragged-dot-metadata.N`` is its small set-up call)
# and the sorted row update of ``ops/row_update.py``.
GROUPED_MATMUL = "ragged-dot"
_CALLS_BY_NAME = ((GROUPED_MATMUL, "matmul"), ("row_update", "scatter_gather"))


@dataclass(frozen=True)
class Event:
    name: str
    start: float          # ns
    end: float            # ns

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclass
class DeviceLines:
    ops: List[Event] = field(default_factory=list)        # "XLA Ops"
    async_ops: List[Event] = field(default_factory=list)  # "Async XLA Ops"
    modules: List[Event] = field(default_factory=list)    # "XLA Modules"


@dataclass
class Trace:
    devices: Dict[str, DeviceLines] = field(default_factory=dict)
    host: List[Event] = field(default_factory=list)       # the python line


def load_xplane(path: str) -> Trace:
    """Read the planes this module uses out of an ``.xplane.pb``."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    trace = Trace()
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = trace.devices.setdefault(plane.name, DeviceLines())
            for line in plane.lines:
                target = {"XLA Ops": dev.ops, "Async XLA Ops": dev.async_ops,
                          "XLA Modules": dev.modules}.get(line.name)
                if target is not None:
                    target.extend(Event(e.name, e.start_ns,
                                        e.start_ns + e.duration_ns)
                                  for e in line.events)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                if line.name == "python":
                    trace.host.extend(Event(e.name, e.start_ns,
                                            e.start_ns + e.duration_ns)
                                      for e in line.events)
    return trace


# ---------------------------------------------------------------- HLO text
_COMPUTATION = re.compile(r"^(?:ENTRY )?%([\w.\-]+) \(.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (.*)$")
_OPCODE = re.compile(r" ([a-z][\w\-]*)\(")
_CALLS = re.compile(r"calls=%([\w.\-]+)")


def _opcode(rhs: str) -> str:
    """Opcode of an instruction's right-hand side: the first ``word(`` after
    a blank.  Result types (``f32[8,128]{1,0:T(8,128)S(1)}``, tuples of
    them) hold no such pattern."""
    m = _OPCODE.search(" " + rhs)
    return m.group(1) if m else ""


class HloIndex:
    """Opcodes inside each computation of the compiled programs' text
    (``compiled.as_text()``), so a fusion can be named for what it does.
    A fused computation may call further fusions: those are followed."""

    def __init__(self, texts: Iterable[str] = ()):
        self.inner: Dict[str, set] = {}
        self.calls: Dict[str, set] = {}
        for text in texts:
            current = None
            for raw in text.splitlines():
                head = _COMPUTATION.match(raw)
                if head:
                    current = head.group(1)
                    self.inner.setdefault(current, set())
                    self.calls.setdefault(current, set())
                elif raw.startswith("}"):
                    current = None
                elif current is not None:
                    ins = _INSTRUCTION.match(raw)
                    if ins:
                        self.inner[current].add(_opcode(ins.group(2)))
                        self.calls[current].update(
                            _CALLS.findall(ins.group(2)))

    def opcodes(self, computation: str) -> set:
        seen, todo, out = set(), [computation], set()
        while todo:
            name = todo.pop()
            if name in seen:
                continue
            seen.add(name)
            out |= self.inner.get(name, set())
            todo.extend(self.calls.get(name, ()))
        out.discard("fusion")
        return out


def instruction_name(event_name: str) -> str:
    """``%fusion.4 = f32[...] fusion(...)`` → ``fusion.4``."""
    m = _INSTRUCTION.match(event_name)
    return m.group(1) if m else event_name


def classify(event_name: str, index: Optional[HloIndex] = None) -> str:
    """Category of one device event, from its HLO instruction text."""
    m = _INSTRUCTION.match(event_name)
    if not m:
        return "other"
    name, rhs = m.groups()
    op = _opcode(rhs)
    if op.startswith("async-"):
        # ``%slice-done.2 = ... async-done(...)``: the wrapped opcode is in
        # the instruction's name.
        op = re.sub(r"-(start|update|done)(\.\d+)?$", "", name)
    if op == "custom-call":
        if 'custom_call_target="tpu_custom_call"' not in rhs:
            return "other"
        return next((cat for stem, cat in _CALLS_BY_NAME
                     if name.startswith(stem)), "mosaic")
    if op.startswith(_COLLECTIVES):
        return "collective"
    if op in _CONTROL:
        return "control"
    if op in ("convolution", "dot"):
        return "matmul"
    if op in ("scatter", "gather", "dynamic-update-slice"):
        return "scatter_gather"
    if op in _MOVES:
        return "copy"
    if op != "fusion":
        return "elementwise" if op else "other"
    calls = _CALLS.search(rhs)
    inner = index.opcodes(calls.group(1)) if index and calls else set()
    if not inner:
        # No program text at hand: XLA names a fusion after what it fuses.
        inner = {part for part in re.split(r"[_.]", name)
                 if part and not part.isdigit()}
        inner.discard("fusion")
    if inner & {"convolution", "dot"}:
        return "matmul"
    if any(o.startswith(_COLLECTIVES) for o in inner):
        return "collective"
    if inner & {"scatter", "gather", "dynamic-update-slice"}:
        return "scatter_gather"
    if inner and inner <= _MOVES:
        return "copy"
    return "elementwise" if inner else "other"


def is_grouped_matmul(event_name: str) -> bool:
    """XLA's grouped-matmul call, which carries no scope and no phase: its
    instruction and its ``op_name`` alike are ``ragged-dot-none.N``."""
    m = _INSTRUCTION.match(event_name)
    return bool(m and m.group(1).startswith(GROUPED_MATMUL)
                and 'custom_call_target="tpu_custom_call"' in m.group(2))


# ---------------------------------------------------------------- intervals
Interval = Tuple[float, float]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merged, sorted, non-overlapping intervals."""
    out: List[List[float]] = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def covered_ns(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in union(intervals))


def subtract(intervals: Iterable[Interval],
             holes: Iterable[Interval]) -> List[Interval]:
    """The parts of ``intervals`` that no interval of ``holes`` covers."""
    holes = union(holes)
    out: List[Interval] = []
    first = 0                       # holes before it end before any interval
    for a, b in union(intervals):
        while first < len(holes) and holes[first][1] <= a:
            first += 1
        at = a
        for h0, h1 in holes[first:]:
            if h0 >= b:
                break
            if h0 > at:
                out.append((at, h0))
            at = max(at, h1)
        if at < b:
            out.append((at, b))
    return out


def _clip(events: Iterable[Event], t0: float, t1: float) -> List[Event]:
    return [Event(e.name, max(e.start, t0), min(e.end, t1))
            for e in events if e.end > t0 and e.start < t1]


def self_times(events: Sequence[Event]) -> List[Tuple[Event, float]]:
    """Each event with the part of its duration no nested event covers
    (events of one line nest: a ``while`` spans its body)."""
    order = sorted(events, key=lambda e: (e.start, -e.end))
    out: List[List] = []
    stack: List[int] = []
    for e in order:
        while stack and out[stack[-1]][0].end <= e.start:
            stack.pop()
        if stack:
            parent = out[stack[-1]]
            parent[1] -= min(e.end, parent[0].end) - e.start
        out.append([e, e.dur])
        stack.append(len(out) - 1)
    return [(e, max(s, 0.0)) for e, s in out]


# ----------------------------------------------------------------- summary
@dataclass
class Summary:
    """One traced window, reduced.  Times in seconds; per-device numbers are
    means over the chips in the trace."""
    window_s: float
    chips: int
    busy_s: float
    by_category_s: Dict[str, float]
    by_op_s: List[Tuple[str, float]]          # (name [category], seconds)
    collective_s: float                       # in flight or waited for
    collective_exposed_s: float               # ... with no compute running
    step_programs: int                        # executions of the step
    gaps_by_host_s: List[Tuple[str, float]]   # idle time by host activity
    longest_gaps: List[Tuple[str, float]]     # single gaps, longest first

    @property
    def idle_s(self) -> float:
        return max(self.window_s - self.busy_s, 0.0)


def _host_label(t0: float, t1: float, host: Sequence[Event]) -> str:
    """What the host was doing during ``[t0, t1]``: the innermost benchmark
    span and the innermost other host event that cover the gap's middle."""
    mid = (t0 + t1) / 2
    span, other = None, None
    for e in host:
        if e.start <= mid < e.end and e.name != WINDOW_SPAN:
            if e.name.startswith(SPAN_PREFIX):
                if span is None or e.dur < span.dur:
                    span = e
            elif other is None or e.dur < other.dur:
                other = e
    return (f"{span.name if span else 'no span'}/"
            f"{other.name if other else 'python'}")


def summarize(trace: Trace, index: Optional[HloIndex] = None,
              step_module: str = "jit_step",
              min_gap_ns: float = 20000.0) -> Optional[Summary]:
    """Reduce the window marked by ``bench.window``; ``None`` when the trace
    holds no device plane or no window (a CPU run: nothing to read).

    Idle gaps shorter than ``min_gap_ns`` are the device's own bubbles
    between operations: they count as idle but are not looked up on the
    host, whose dispatch latencies are tens of microseconds and more."""
    windows = [e for e in trace.host if e.name == WINDOW_SPAN]
    if not trace.devices or not windows:
        return None
    t0 = min(w.start for w in windows)
    t1 = max(w.end for w in windows)
    chips = len(trace.devices)
    busy = 0.0
    cats = {c: 0.0 for c in CATEGORIES}
    ops: Dict[str, float] = {}
    coll = exposed = 0.0
    programs = 0
    first = None
    category: Dict[str, str] = {}

    def cat_of(name: str) -> str:
        if name not in category:
            category[name] = classify(name, index)
        return category[name]

    host = [e for e in trace.host if e.end > t0 and e.start < t1]
    for key in sorted(trace.devices):
        dev = trace.devices[key]
        dev_ops = _clip(dev.ops, t0, t1)
        busy_iv = union((e.start, e.end) for e in dev_ops)
        busy += sum(b - a for a, b in busy_iv)
        compute_iv, coll_iv = [], []
        for e, self_ns in self_times(dev_ops):
            cat = cat_of(e.name)
            cats[cat] += self_ns
            label = f"{instruction_name(e.name)} [{cat}]"
            ops[label] = ops.get(label, 0.0) + self_ns
            if cat == "collective":
                coll_iv.append((e.start, e.end))
            elif cat != "control":
                compute_iv.append((e.start, e.end))
        coll_iv += [(e.start, e.end) for e in _clip(dev.async_ops, t0, t1)
                    if cat_of(e.name) == "collective"]
        coll += covered_ns(coll_iv)
        exposed += sum(b - a for a, b in subtract(coll_iv, compute_iv))
        programs += sum(1 for e in _clip(dev.modules, t0, t1)
                        if e.name.startswith(step_module))
        if first is None:
            first = subtract([(t0, t1)], busy_iv)
    by_host: Dict[str, float] = {}
    singles: List[Tuple[str, float]] = []
    for a, b in first or []:
        if b - a < min_gap_ns:
            label = f"gaps under {min_gap_ns / 1e3:g} us"
        else:
            label = _host_label(a, b, host)
            singles.append((label, (b - a) / 1e9))
        by_host[label] = by_host.get(label, 0.0) + (b - a) / 1e9
    return Summary(
        window_s=(t1 - t0) / 1e9, chips=chips, busy_s=busy / chips / 1e9,
        by_category_s={c: v / chips / 1e9 for c, v in cats.items()},
        by_op_s=sorted(((k, v / chips / 1e9) for k, v in ops.items()),
                       key=lambda kv: -kv[1]),
        collective_s=coll / chips / 1e9,
        collective_exposed_s=exposed / chips / 1e9,
        step_programs=programs // chips,
        gaps_by_host_s=sorted(by_host.items(), key=lambda kv: -kv[1]),
        longest_gaps=sorted(singles, key=lambda kv: -kv[1]))


def breakdown(summary: Summary) -> dict:
    """The contract's ``breakdown``: the ten device operations with most
    time, and idle time by what the host was doing (totals by host activity,
    then the longest single gaps), ten entries at most each."""
    gaps = [[f"total: {k}", v] for k, v in summary.gaps_by_host_s[:5]]
    gaps += [[f"longest: {k}", v] for k, v in summary.longest_gaps[:5]]
    return {"device_ops": [[k, v] for k, v in summary.by_op_s[:10]],
            "idle_gaps": gaps}
