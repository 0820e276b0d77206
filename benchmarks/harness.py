"""What every cell shares: finding a cell's files by name, the device check,
the set-up clock, compile counting, the profiler window, the per-layer
readers and the result line.

A cell is an entry of ``workloads`` in ``BENCHMARK.json``.  Everything that
belongs to one configuration, traffic mix, runner, generator, reference or
per-layer metric sits in a file of its own, found by name under the
directories ``BENCHMARK.json`` lists in ``paths`` (and, last, this one):

    configs/<config>.json          sizes as run; names its runner, reference
    traffic/<traffic>.json         parameters one general generator reads
    runners/<runner>.py            setup(cell, rt) -> session.measure(rt)
    generators/<generator>.py      seeded inputs
    reference/<reference>.py       the plain implementation ``correct`` uses
    layer_metrics/*.py             one reader per per-layer metric

so a later PR adds a cell, a metric or a model by adding files and entries.
"""

from __future__ import annotations

import glob
import importlib.util
import json
import os
import shutil
import sys
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)

__all__ = ["Cell", "Runtime", "Measured", "Reading", "load_cell",
           "load_module", "layer_readers", "reader_applies", "compared",
           "run_cell", "main", "REPO", "HERE"]

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"


# ------------------------------------------------------------------- cells
@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: Tuple[dict, ...]      # this cell's metric declarations
    per_layer: Tuple[dict, ...]
    search: Tuple[str, ...]           # directories its files are looked up in

    @property
    def runner(self) -> str:
        return self.config["runner"]


def _find(search, kind: str, filename: str) -> str:
    for base in search:
        path = os.path.join(base, kind, filename)
        if os.path.isfile(path):
            return path
    raise FileNotFoundError(
        f"no {kind}/{filename} under any of {list(search)}")


def _in_cell(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def load_cell(name: str, root: str = REPO) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its files read."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    rows = [w for w in bench["workloads"] if w["name"] == name]
    if not rows:
        raise KeyError(f"BENCHMARK.json has no workload {name!r} (has: "
                       f"{[w['name'] for w in bench['workloads']]})")
    row = rows[0]
    search = tuple(os.path.join(root, p) for p in bench["paths"])
    if HERE not in search:
        search += (HERE,)
    declared = {c["name"]: c for c in bench["configs"]}[row["config"]]
    with open(os.path.join(root, declared["file"])) as f:
        config = json.load(f)
    with open(_find(search, "traffic", row["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return Cell(
        name=name, chips=int(row["chips"]), config=config, traffic=traffic,
        end_to_end=tuple(m for m in bench["end_to_end"] if _in_cell(m, name)),
        per_layer=tuple(m for m in bench["per_layer"] if _in_cell(m, name)),
        search=search)


def load_module(search, kind: str, name: str):
    """Import ``<kind>/<name>.py`` from the first search directory that has
    it, under a module name of its own."""
    path = _find(search, kind, name + ".py")
    modname = f"benchmarks_{kind}_{name}".replace("-", "_").replace(".", "_")
    if modname in sys.modules and getattr(
            sys.modules[modname], "__file__", None) == path:
        return sys.modules[modname]
    spec = importlib.util.spec_from_file_location(modname, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[modname] = module
    spec.loader.exec_module(module)
    return module


def layer_readers(search) -> Dict[str, Any]:
    """Every per-layer reader under ``layer_metrics/``, by metric name."""
    readers: Dict[str, Any] = {}
    for base in search:
        for path in sorted(glob.glob(os.path.join(base, "layer_metrics",
                                                  "*.py"))):
            stem = os.path.splitext(os.path.basename(path))[0]
            if stem.startswith("_"):
                continue
            module = load_module((base,), "layer_metrics", stem)
            readers.setdefault(module.NAME, module)
    return readers


def reader_applies(applies: dict, config: dict, chips: int) -> bool:
    """Whether a reader's ``APPLIES`` holds for a cell, by the cell's data
    alone and never by its name: ``runner`` (the configuration's runner, one
    name or several), ``min_chips``, and ``model`` (keys of the
    configuration's ``model`` group that have to be set, ``True``, or left
    out or falsy, ``False``).  Among the cells that report the end-to-end
    metric the reader's metric moves, these are its ``workloads``
    (``tests/test_contract.py`` holds every entry to that)."""
    runners = applies.get("runner", config.get("runner"))
    if isinstance(runners, str):
        runners = (runners,)
    model = config.get("model", {})
    return (config.get("runner") in runners
            and chips >= applies.get("min_chips", 1)
            and all(bool(model.get(key)) == want
                    for key, want in applies.get("model", {}).items()))


# ----------------------------------------------------------------- runtime
class CompileWatch:
    """Counts what JAX compiles or fetches from its cache, by listening to
    JAX's own monitoring events."""

    def __init__(self):
        import jax.monitoring

        self.backend_compiles = 0      # compile-or-load, one per program
        self.cache_misses = 0          # of those, compiled from nothing
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def close(self) -> None:
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)

    def _duration(self, event: str, secs: float, **kw) -> None:
        if event == BACKEND_COMPILE_EVENT:
            self.backend_compiles += 1

    def _event(self, event: str, **kw) -> None:
        if event == CACHE_MISS_EVENT:
            self.cache_misses += 1


@dataclass
class Runtime:
    """What the harness hands a runner."""
    seed: int
    seconds: float
    trace: bool
    t_start: float                       # process start, perf_counter clock
    devices: List[Any]                   # the chips this cell uses
    watch: Optional[CompileWatch] = None
    setup_s: Optional[float] = None
    compiles_at_open: int = 0
    compiles_in_window: int = 0

    def span(self, name: str):
        import jax

        return jax.profiler.TraceAnnotation(name)

    def log(self, **fields) -> None:
        """One JSON object on a line of its own, before the result line."""
        print(json.dumps(fields, default=float), flush=True)

    def open_window(self) -> float:
        """Set-up ends here: everything before is ``setup_s``."""
        now = time.perf_counter()
        self.setup_s = now - self.t_start
        self.compiles_at_open = self.watch.backend_compiles
        return now

    def close_window(self) -> None:
        self.compiles_in_window = (self.watch.backend_compiles
                                   - self.compiles_at_open)


@dataclass
class Measured:
    """What a runner's ``measure`` returns.  ``setup_s`` and
    ``peak_hbm_gib`` are every cell's and are added by the harness."""
    attempted: int
    failed: int
    end_to_end: Dict[str, float]                 # the runner's own metrics
    checks: Dict[str, bool]                      # all true <=> correct
    compiled_peak_bytes: int                     # the step's compiler account
    facts: Dict[str, Any] = field(default_factory=dict)
    hlo_texts: List[str] = field(default_factory=list)
    # what the reference check compared: name -> (reading, its limit)
    compared: Dict[str, Tuple[float, float]] = field(default_factory=dict)


@dataclass
class Reading:
    """What a per-layer reader is given.  ``trace`` is ``None`` where the
    run left no device trace (off the chip)."""
    facts: Dict[str, Any]
    trace: Any                       # benchmarks.trace.reduce.Summary | None
    peaks: Dict[str, Any]
    compiles_in_window: int


def compared(check: dict, pairs) -> Dict[str, Tuple[float, float]]:
    """The numbers a runner's reference check compared, each beside its
    limit, for the result line: ``pairs`` are (key of the reading, key of its
    limit) in ``check``, a dot for a nested group; a reading that is a dict
    of readings stands by its largest."""
    def at(path):
        found = check
        for key in path.split("."):
            found = found[key]
        return max(found.values()) if isinstance(found, dict) else found

    return {value: (float(at(value)), float(at(limit)))
            for value, limit in pairs}


# ------------------------------------------------------------------ device
def require_tpu(chips: int):
    """The cell's chips, or exit non-zero: no CPU fallback."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"benchmarks/run.py: refusing to run: JAX's first device is "
                 f"on platform {devices[0].platform!r}, need 'tpu'")
    if len(devices) < chips:
        sys.exit(f"benchmarks/run.py: the cell needs {chips} chip(s), JAX "
                 f"found {len(devices)}")
    return devices[:chips]


def compiled_peak_bytes(compiled) -> int:
    """Peak device memory of one compiled program by the compiler's own
    account, per device.  ``peak_memory_in_bytes`` where this jaxlib gives
    it; else arguments + outputs + temporaries - aliased."""
    mem = compiled.memory_analysis()
    peak = int(getattr(mem, "peak_memory_in_bytes", 0) or 0)
    if peak:
        return peak
    return int(mem.argument_size_in_bytes + mem.output_size_in_bytes
               + mem.temp_size_in_bytes - mem.alias_size_in_bytes)


def _device_line(devices, compiled_peak: int) -> dict:
    import jax

    stats = [d.memory_stats() or {} for d in devices]
    runtime_peak = max((s.get("peak_bytes_in_use", 0) for s in stats),
                       default=0)
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(jax.devices()),
            # The runtime's counter does not see a program's temporaries on
            # one chip (PERF.md, PR 21), so the compiler's account of the
            # step stands beside it and the larger is reported.
            "memory_peak_bytes": int(max(runtime_peak, compiled_peak))}


# ------------------------------------------------------------------- a run
def _start_trace(cell: Cell, out_root: str) -> str:
    import jax

    trace_dir = os.path.join(out_root, ".bench_out", "trace", cell.name)
    shutil.rmtree(trace_dir, ignore_errors=True)
    os.makedirs(trace_dir, exist_ok=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0       # TraceAnnotation spans only
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    return trace_dir


def _stop_trace(trace_dir: str) -> Optional[str]:
    import jax

    jax.profiler.stop_trace()
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def _read_layers(cell: Cell, reading: Reading) -> Dict[str, float]:
    """The cell's per-layer metrics, each from its reader; one that finds
    nothing to read is left out."""
    readers = layer_readers(cell.search)
    metrics = {}
    for m in cell.per_layer:
        if m["name"] not in readers:
            raise KeyError(f"no reader under layer_metrics/ for {m['name']!r}")
        value = readers[m["name"]].read(reading)
        if value is not None:
            metrics[m["name"]] = float(value)
    return metrics


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, rehearsal: bool = False,
             out_root: str = REPO) -> dict:
    """Set up, measure and reduce one run of ``cell``; returns the result
    line as a dict.  ``rehearsal`` is the tests' entry: it skips the TPU
    refusal and prints no timing, rate or share, only program counts."""
    import jax

    from multiverso_tpu import compile_cache

    from benchmarks import peaks as peaks_table
    from benchmarks.trace import reduce as trace_reduce

    if rehearsal:
        devices = jax.devices()[:cell.chips]
        if len(devices) < cell.chips:
            raise RuntimeError(f"rehearsal of {cell.name} needs {cell.chips} "
                               f"devices, found {len(devices)}")
        peaks: Dict[str, Any] = {}
    else:
        devices = require_tpu(cell.chips)
        peaks = peaks_table.peaks_for(devices[0].device_kind)
    cache_dir = compile_cache.configure()     # the program's own placement
    if not rehearsal:
        # Small programs too, so a warm run compiles nothing at all.
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    rt = Runtime(seed=seed, seconds=float(seconds), trace=trace,
                 t_start=t_start, devices=list(devices),
                 watch=CompileWatch())
    if trace:
        rt.seconds = min(rt.seconds,
                         float(cell.traffic.get("trace_seconds", rt.seconds)))
    rt.log(cell=cell.name, seed=seed, seconds=rt.seconds, trace=trace,
           platform=devices[0].platform, device_kind=devices[0].device_kind,
           device_count=len(jax.devices()), chips=cell.chips,
           compile_cache=cache_dir)

    runner = load_module(cell.search, "runners", cell.runner)
    trace_dir = None
    try:
        session = runner.setup(cell, rt)
        trace_dir = _start_trace(cell, out_root) if trace else None
        measured: Measured = session.measure(rt)
    finally:
        xplane = _stop_trace(trace_dir) if trace_dir else None
        rt.watch.close()
    if rt.setup_s is None:
        raise RuntimeError(f"runner {cell.runner} never opened its window")
    rt.log(setup_s=rt.setup_s, compiles_in_setup=rt.compiles_at_open,
           cold_compiles=rt.watch.cache_misses,
           compiles_in_window=rt.compiles_in_window)

    checks = dict(measured.checks)
    checks["no compile in the window"] = rt.compiles_in_window == 0
    values = dict(measured.end_to_end, setup_s=rt.setup_s,
                  peak_hbm_gib=measured.compiled_peak_bytes / 2 ** 30)
    result: Dict[str, Any] = {
        "correct": all(checks.values()),
        "attempted": int(measured.attempted),
        "failed": int(measured.failed)}
    if not result["correct"]:
        rt.log(failed_checks=[k for k, ok in checks.items() if not ok])

    summary = None
    if trace:
        declared = cell.per_layer
        if xplane:
            summary = trace_reduce.summarize(
                trace_reduce.load_xplane(xplane),
                trace_reduce.HloIndex(measured.hlo_texts))
        if summary is not None:         # None: the trace has no device plane
            rt.log(trace_categories_s=summary.by_category_s,
                   trace_step_programs=summary.step_programs)
        metrics = _read_layers(cell, Reading(
            facts=measured.facts, trace=summary, peaks=peaks,
            compiles_in_window=rt.compiles_in_window))
    else:
        declared = cell.end_to_end
        metrics = {m["name"]: float(values[m["name"]]) for m in declared
                   if m["name"] in values}
    if rehearsal:
        # Off the chip only what the program counts may be printed.
        metrics = {m["name"]: metrics[m["name"]] for m in declared
                   if m["source"] == "program_counter"
                   and m["name"] in metrics}
    units = {m["name"]: m["unit"] for m in declared}
    result["metrics"] = {k: {"value": v, "unit": units[k]}
                         for k, v in metrics.items()}
    result["device"] = _device_line(devices, measured.compiled_peak_bytes)
    if summary is not None and not rehearsal:
        result["device"]["busy_s"] = summary.busy_s
        result["device"]["window_s"] = summary.window_s
        result["breakdown"] = trace_reduce.breakdown(summary)
    # last on the line: every number ``correct`` compared, beside its limit
    result["compared"] = {k: {"value": v, "limit": limit}
                          for k, (v, limit) in measured.compared.items()}
    result["compared"]["compiles_in_window"] = {
        "value": rt.compiles_in_window, "limit": 0}
    return result


def main(argv, t_start: float) -> int:
    import argparse

    ap = argparse.ArgumentParser(prog="benchmarks/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      t_start)
    print(json.dumps(result), flush=True)
    for name, pair in result["compared"].items():      # stderr's last lines
        print(f"compared {name}: {pair['value']!r} (limit {pair['limit']!r})",
              file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    return 0
