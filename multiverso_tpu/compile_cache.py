"""Where JAX keeps compiled programs between processes, and what compiling
cost this one.

A cold ~1B-parameter train step takes minutes to compile, and every new
process would pay it again.  JAX's persistent compilation cache keys each
entry on the program AND on the cache path, so the directory must not
move between runs: it is ``JAX_COMPILATION_CACHE_DIR`` when the
environment sets it (jax reads that variable itself — nothing to do
here), else ``<checkout>/.jax_cache`` beside the package.  ``init()``
calls :func:`configure`; no other code sets a cache directory.

**The compile account** (docs/observability.md, "Start-up").
:func:`configure` also installs, once, a listener on JAX's own monitoring
events.  JAX sends the traced function's name (``fun_name``) with the
three durations of a program's way to the device, so each is booked by
program: the Dashboard monitors ``jax::trace``, ``jax::lower`` and
``jax::compile_or_load`` (one observation a program; a fetch from the
persistent cache is inside it, and is ``jax::cache_load`` besides), the
counter ``compile.cache{result=hit|miss}``, with tracing on a finished
span a duration (args ``fun``, and ``cache`` where the request's hit or
miss is known), and :func:`account`'s totals.  The hit, miss and retrieval
events carry no name: they arrive inside their program's
``backend_compile_duration`` and are held for it on the compiling thread.
"""

from __future__ import annotations

import os
import re
import threading
from typing import Any, Dict

import jax
import jax.monitoring

from . import dashboard, metrics, tracing
from .log import Log

__all__ = ["configure", "account", "report", "DEFAULT_DIR"]

DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")

# JAX's event -> (monitor, the account's key).
_DURATIONS = {
    "/jax/core/compile/jaxpr_trace_duration": ("jax::trace", "trace_s"),
    "/jax/core/compile/jaxpr_to_mlir_module_duration":
        ("jax::lower", "lower_s"),
    "/jax/core/compile/backend_compile_duration":
        ("jax::compile_or_load", "compile_or_load_s"),
    "/jax/compilation_cache/cache_retrieval_time_sec":
        ("jax::cache_load", "cache_load_s"),
}
# ... -> (the counter's ``result`` label, the account's key).
_RESULTS = {"/jax/compilation_cache/cache_hits": ("hit", "hits"),
            "/jax/compilation_cache/cache_misses": ("miss", "misses")}
_KEYS = ("trace_s", "lower_s", "compile_or_load_s", "cache_load_s",
         "programs", "hits", "misses")
_MODULE_NAME = re.compile(r"^\w+\((.*)\)$")
REPORTED_PROGRAMS = 5

_LOCK = threading.Lock()
_INSTALLED = False
_TOTALS: Dict[str, float] = dict.fromkeys(_KEYS, 0)
_BY_FUN: Dict[str, Dict[str, float]] = {}   # one entry a distinct fun_name
_PENDING = threading.local()    # this thread's request: .result, .load_s


def _on_duration(event: str, secs: float, **kw: Any) -> None:
    booked = _DURATIONS.get(event)
    if booked is None:
        return
    monitor, key = booked
    dashboard.get_monitor(monitor).observe(secs)
    if key == "cache_load_s":               # nameless: held for its program
        _PENDING.load_s = secs
        return
    # Tracing names the function (``step``), lowering and compiling the
    # module (``jit(step)``): one program, booked under the function's name.
    fun = str(kw.get("fun_name", "?"))
    wrapped = _MODULE_NAME.match(fun)
    if wrapped:
        fun = wrapped.group(1)
    add = {key: secs}
    args = {"fun": fun}
    if key == "compile_or_load_s":
        add["programs"] = 1
        add["cache_load_s"] = getattr(_PENDING, "load_s", 0.0)
        result = getattr(_PENDING, "result", None)
        if result is not None:
            args["cache"], counted = result
            add[counted] = 1
        _PENDING.load_s, _PENDING.result = 0.0, None
    with _LOCK:
        mine = _BY_FUN.setdefault(fun, dict.fromkeys(_KEYS, 0))
        for k, v in add.items():
            _TOTALS[k] += v
            mine[k] += v
    tracing.record_ended(monitor, secs, args=args)


def _on_event(event: str, **kw: Any) -> None:
    result = _RESULTS.get(event)
    if result is None:
        return
    metrics.counter("compile.cache", {"result": result[0]}).inc()
    _PENDING.result = result


def configure() -> str:
    """Place the compile cache and install the compile account's listeners
    (once, however often this is called); returns the directory in
    effect."""
    global _INSTALLED
    with _LOCK:
        if not _INSTALLED:
            jax.monitoring.register_event_duration_secs_listener(_on_duration)
            jax.monitoring.register_event_listener(_on_event)
            _INSTALLED = True
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR


def account() -> Dict[str, Any]:
    """What compiling cost this process since :func:`configure`: seconds
    tracing (``trace_s``), lowering (``lower_s``), compiling or fetching
    (``compile_or_load_s``, which holds ``cache_load_s``), the ``programs``
    that took that way, and of the requests that asked the persistent cache
    its ``hits`` and ``misses``; ``by_fun`` holds the same keys for each
    ``fun_name``.  A plain copy."""
    with _LOCK:
        return dict(_TOTALS, by_fun={f: dict(v) for f, v in _BY_FUN.items()})


def report() -> None:
    """The account through the logger, beside the Dashboard's table at
    shutdown: the totals, then the programs a start waited longest for."""
    acc = account()
    if not acc["programs"]:
        return
    Log.info("compile account: %d program(s), %d hit / %d miss; trace "
             "%.3fs lower %.3fs compile_or_load %.3fs (cache_load %.3fs)",
             acc["programs"], acc["hits"], acc["misses"], acc["trace_s"],
             acc["lower_s"], acc["compile_or_load_s"], acc["cache_load_s"])
    slowest = sorted((kv for kv in acc["by_fun"].items()
                      if kv[1]["programs"]),
                     key=lambda kv: -kv[1]["compile_or_load_s"])
    for fun, v in slowest[:REPORTED_PROGRAMS]:
        Log.info("  %s: x%d compile_or_load %.3fs trace %.3fs lower %.3fs",
                 fun, v["programs"], v["compile_or_load_s"], v["trace_s"],
                 v["lower_s"])
