"""Dashboard: named timing monitors — now a shim over the metrics
registry (docs/observability.md).

Parity with the reference's ``dashboard.h`` / ``src/dashboard.cpp``
(``Dashboard``, ``Monitor``, ``MONITOR(...)`` macro; SURVEY.md §2.26):
named accumulating timers around hot paths, aggregated and dumped at
shutdown through the logger.  The ``monitor()`` / ``get_monitor()`` /
``report()`` surface is unchanged, but every monitor is now backed by a
:class:`multiverso_tpu.metrics.Histogram` (fixed log2 latency buckets),
so ``report()`` prints p50/p95/p99 and ``metrics.snapshot()`` exposes
every monitor alongside the counters/gauges of the rest of the system.

Each monitored section runs under ``tracing.span``: table ops,
barriers and jitted steps show up on the merged timeline when tracing
is armed (``-trace_dir`` / ``tracing.enable()``), and on the host line
of any ``jax.profiler`` capture, without new call sites.

TPU-native addition: monitors can also wrap jitted calls (timing includes
``block_until_ready``).
"""

from __future__ import annotations

import threading
import time
from contextlib import contextmanager
from typing import Any, Dict, Iterator

from . import metrics, tracing
from .log import Log

__all__ = ["Monitor", "monitor", "get_monitor", "report", "reset", "ended"]


class Monitor:
    """Accumulating named timer over a registry histogram.

    Keeps the legacy surface (``count`` / ``total_s`` / ``max_s`` /
    ``mean_ms``) and adds bucket percentiles (``p50_ms`` ...).
    """

    def __init__(self, name: str):
        self.name = name
        self._hist = metrics.histogram(name)

    def observe(self, seconds: float) -> None:
        """Book a section timed elsewhere (the package's import, a
        duration JAX reports): ``monitor()`` without the body."""
        self._hist.observe(seconds)

    @property
    def count(self) -> int:
        return self._hist.count

    @property
    def total_s(self) -> float:
        return self._hist.sum

    @property
    def max_s(self) -> float:
        return self._hist.max

    @property
    def mean_ms(self) -> float:
        return self._hist.mean * 1e3

    def quantile_ms(self, q: float) -> float:
        return self._hist.quantile(q) * 1e3

    @property
    def p50_ms(self) -> float:
        return self.quantile_ms(0.50)

    @property
    def p95_ms(self) -> float:
        return self.quantile_ms(0.95)

    @property
    def p99_ms(self) -> float:
        return self.quantile_ms(0.99)

    def __str__(self) -> str:
        return (f"{self.name}: count={self.count} total={self.total_s:.3f}s "
                f"mean={self.mean_ms:.3f}ms p50={self.p50_ms:.3f}ms "
                f"p95={self.p95_ms:.3f}ms p99={self.p99_ms:.3f}ms "
                f"max={self.max_s * 1e3:.3f}ms")


_LOCK = threading.Lock()
_MONITORS: Dict[str, Monitor] = {}
# What the last reset() cleared: one lifecycle's monitors, replaced by
# the next reset().
_ENDED: Dict[str, Monitor] = {}


def get_monitor(name: str) -> Monitor:
    with _LOCK:
        m = _MONITORS.get(name)
        if m is None:
            m = _MONITORS[name] = Monitor(name)
        return m


@contextmanager
def monitor(name: str, **args: Any) -> Iterator[Monitor]:
    """``with dashboard.monitor("Worker::Get"):`` — the MONITOR macro.

    The section runs under a span context, so with tracing armed
    nested monitors (and native calls the caller stamps via
    ``NativeRuntime.set_trace_id``) share its trace id.  ``args`` go to
    the span (``rows=``, ``steps=``); the timer keeps none.
    """
    m = get_monitor(name)
    with tracing.span(name, **args):
        t0 = time.perf_counter()
        try:
            yield m
        finally:
            m._hist.observe(time.perf_counter() - t0)


def report(log: bool = True) -> Dict[str, Monitor]:
    """Aggregate table; dumped at shutdown like the reference Dashboard
    (now with percentiles)."""
    with _LOCK:
        monitors = dict(_MONITORS)
    if log and monitors:
        Log.info("---------------- Dashboard ----------------")
        for name in sorted(monitors):
            Log.info("  %s", monitors[name])
        Log.info("--------------------------------------------")
    return monitors


def reset() -> None:
    """Drop every monitor from the table and the registry.  What they had
    accumulated stays readable through :func:`ended` until the next
    reset."""
    global _ENDED
    with _LOCK:
        for name in _MONITORS:
            metrics.REGISTRY.remove(name)
        _ENDED = dict(_MONITORS)
        _MONITORS.clear()


def ended() -> Dict[str, Monitor]:
    """The monitors of the lifecycle the last :func:`reset` closed:
    ``shutdown()`` resets, and a job's start-up sections (the package's
    import, a table's placement) are still a fact of the process after
    it."""
    with _LOCK:
        return dict(_ENDED)
