#!/usr/bin/env python
"""mvlint — repo-specific AST lint for the multiverso_tpu Python layer.

Generic linters cannot see this repo's invariants; these rules encode
the ones that have bitten (or nearly bitten) real code here.  Run as
``python tools/mvlint.py [paths...]`` (default: the repo root); exits
non-zero on any finding.  ``make mvlint`` / ``make lint`` wrap this, and
``tests/test_static_analysis.py`` keeps it green in tier-1.

Rules (docs/static_analysis.md has the full rationale):

- **MV001 ctypes-temporary** — an argument built as ``_fp(expr)`` /
  ``_ip(expr)`` / ``expr.ctypes.data_as(...)`` must take a *name*, not a
  temporary: the pointer outlives the expression only if a Python
  reference keeps the numpy buffer alive (async natives scatter into it
  after the call returns; a temporary's buffer is freed memory by then).

- **MV002 dangling-async** — a ``*_async(...)`` call whose handle is
  discarded can never be waited or cancelled: the request stays
  in-flight against a buffer nobody owns.  Bind the handle; ``wait()``
  it or drop it explicitly (``del``) so ``__del__`` withdraws the
  ticket.

- **MV003 host-sync-in-jit** — ``np.asarray`` / ``.block_until_ready``
  / ``jax.device_get`` / ``.item`` inside a jit-traced function in the
  tables layer either breaks tracing or silently forces a host sync per
  step; hoist it out of the traced body.

- **MV004 unbounded-subprocess** — bench sections must bound every
  subprocess (``timeout=`` on ``subprocess.run``-family calls and on
  ``.communicate()``/``.wait()``): a hung child otherwise wedges the
  whole bench run instead of costing one section.

- **MV005 unbounded-retry** — runtime code (not tests) may not spin a
  ``while True`` loop whose broad ``except``/``except Exception``
  swallows every failure with no exit (no ``break``/``return``/
  ``raise`` anywhere in the loop): a persistent error then becomes a
  silent busy-loop forever.  Bound it — ``fault.RetryPolicy`` is the
  house schedule (attempt cap + exponential backoff + deadline).

- **MV006 print-in-library** — library code (the ``multiverso_tpu``
  package, minus the executable ``apps/`` worker scripts) must not call
  ``print()`` or mint ad-hoc loggers via ``logging.getLogger(__name__)``
  / ``logging.getLogger()``: output that bypasses
  ``multiverso_tpu.log.Log`` ignores the ``-log_level``/``-log_file``
  flags, interleaves across ranks, and is invisible to the file sink a
  postmortem reads.  Route through ``Log`` (named getLogger calls with
  an explicit sink string — ``log.py`` itself — stay legal).

- **MV007 unbounded-client-cache** — library code may not grow a
  client-side cache/queue without a size bound: a ``self.*cache*`` /
  ``self.*queue*`` attribute initialized to a bare ``{}`` / ``dict()``
  / ``OrderedDict()`` / ``deque()`` (no ``maxlen``) in a class showing
  no eviction evidence (no ``popitem``/``maxlen``/``max_entries``/
  ``capacity``/``evict`` anywhere in the class) accumulates forever
  under serve-style traffic and OOMs the process.  Bound it (the serve
  layer's ``VersionedLRUCache`` is the house pattern) or annotate WHY
  the growth is bounded with a suppression comment.

- **MV008 noncontiguous-ctypes** — a numpy array handed to a ctypes
  float/int pointer (``_fp(x)`` / ``_ip(x)`` / ``x.ctypes.data_as``)
  must have a *provably C-contiguous* producer in the same function
  (``np.ascontiguousarray``, a fresh constructor like ``np.zeros``,
  ``.ravel()``, ``_f32``...).  ``.ctypes`` on a possibly-strided view
  (slices, transposes, parameters of unknown provenance) silently hands
  the native side a pointer whose memory layout does not match the
  declared flat buffer — reads scramble, writes corrupt.

- **MV009 blocking-socket-in-reactor** — native files marked
  ``mvlint: reactor-context`` (the epoll event-loop sources,
  docs/transport.md) may not issue blocking socket calls: every
  ``recv``/``send``/``sendmsg``/``sendto`` must carry ``MSG_DONTWAIT``
  (within the statement) and ``accept``/``accept4``/``connect`` must be
  nonblocking (``SOCK_NONBLOCK``) or suppressed with an explanation — a
  single blocking call inside a reactor parks EVERY connection on that
  shard.  This is the one rule that lints C++ (line-level, not AST);
  the marker comment opts a file in.

- **MV010 observability-bypass** — library code must feed the unified
  observability plane (docs/observability.md), not route around it:
  (a) instantiating ``metrics.Counter``/``Gauge``/``Histogram``
  directly mints a series OUTSIDE the process registry — it never
  reaches ``snapshot()``, the Prometheus flush, or the in-band
  ``OpsQuery`` scrape; use ``metrics.counter()/gauge()/histogram()``.
  (b) a ``with tracing.span(...) as tid:`` that never USES the bound id
  captured a trace id only to drop it — the id exists to be propagated
  (``NativeRuntime.set_trace_id``, a wire message header, a log line);
  either propagate it or drop the ``as`` clause (nested spans inherit
  the thread-local id without it).

- **MV011 per-key-label-cardinality** — a registry series may not be
  labeled by a table key / row id: ``metrics.counter(...,
  labels={"row": row_id})`` mints one series per key, and a sparse
  table has millions — the registry's cardinality cap collapses them
  into one useless overflow series (and before the cap, the registry
  IS the leak).  Per-key accounting belongs in a bounded sketch
  (``multiverso_tpu/sketch.py`` — space-saving top-K / count-min), not
  in label sets; label by bounded dimensions (table name, rank, dir).
  Fires when a ``labels=`` dict value's expression derives from an
  identifier that names a key/row (``key``, ``row``, ``row_id``,
  ``word``, ``token``...), including through ``str()`` / f-strings.

- **MV012 bridge-copy-churn** — an argument flowing into a native
  bridge add/get call (``rt.array_add(...)``, ``matrix_get_rows(...)``,
  raw ``lib.MV_Add*``/``MV_Get*``...) may not be minted INLINE by
  ``astype(...)`` / ``.copy()`` / ``np.ascontiguousarray(...)``: that
  is a full-payload copy per call on the exact path the host-bridge
  fast path exists to de-copy (docs/host_bridge.md).  Allocate the
  buffer once through ``rt.arena().alloc(...)`` and pass it with
  ``borrowed=``/``out=`` (zero-copy, layout guaranteed by
  construction), or hoist the conversion out of the hot loop.  Tests
  are exempt; a genuinely-required copy carries a suppression with its
  why.

- **MV013 row-at-a-time-table-loop** — app/model code (``apps/``,
  ``models/``) may not fetch or push table rows ONE AT A TIME inside a
  Python loop over ids (``for i in ids: t.get_rows([i])`` /
  ``t.add_rows([i], d)`` / ``kv.get([k])`` / ``kv.add({k: v})``): every
  iteration pays a full monitor/serve/wire round trip that the batched
  ``rows=``/``keys=`` call amortizes — at embedding scale the loop is
  the difference between one gather and ten thousand
  (docs/embedding.md).  Batch the ids and call once.

- **MV014 wall-clock-interval** — library code may not measure an
  INTERVAL with a non-monotonic clock: ``t0 = time.time()`` ... ``dur =
  time.time() - t0`` (or ``datetime.now()``/``utcnow()`` differences)
  jumps with NTP steps and DST — on exactly the paths the latency plane
  (docs/observability.md) depends on, a stepped clock turns into a
  phantom p99 spike or a negative stage.  Use ``time.monotonic()`` /
  ``time.monotonic_ns()`` / ``time.perf_counter()`` for durations;
  ``time.time()`` stays legal as a wall-clock TIMESTAMP (trace event
  times, log lines) — only clock-minus-clock subtraction fires.

- **MV015 swallowed-native-exception** — library code may not wrap
  native-call / wire / table operations in an ``except`` whose body
  only ``pass``es (or only logs): those are exactly the paths whose
  failures the delivery-audit plane (docs/observability.md "audit
  plane") exists to surface — a swallowed send error IS a silently
  lost add.  Cleanup idioms stay legal (a ``try`` whose only calls are
  ``close()``/``shutdown()``-style teardown), as does any handler that
  re-raises, returns, falls back, or otherwise *handles*.  Suppress a
  deliberate drop with the standard marker and a reason.

- **MV016 serve-read-without-deadline** — a serve-protocol READ minted
  without a deadline stamp: ``pack_frame(MSG["RequestGet" |
  "RequestVersion" | "RequestReplica"], ...)`` with no ``qos=`` kwarg
  bypasses deadline propagation (docs/serving.md "tail") — the server
  cannot drop the read once its caller has given up, so an abandoned
  request still burns an apply slot at exactly the moment the tier is
  drowning.  Stamp ``qos=(class_id, budget_ns)`` (``AnonServeClient``
  does it for you when a class is declared); suppress only where an
  unstamped pre-13 frame is the point (version-tolerance tests, the
  stamp-overhead A/B baseline).  Tests are out of scope.

- **MV017 stale-shard-route** — code that computes a table→shard
  routing decision (a rank/owner from ``row % shards``-style math or a
  placement lookup like ``server_rank()`` / ``shard_owner()`` /
  ``OwnerOf``) and then carries it across wire calls WITHOUT ever
  re-checking the routing epoch: after a failover promotion or an
  elastic join the shard→rank map flips (docs/replication.md), and a
  cached pre-flip route sends traffic at a corpse — the retry storm
  the epoch broadcast exists to prevent.  Consult
  ``routing_epoch()`` / ``note_routing_epoch()`` /
  ``_check_routing_epoch()`` in the same function (re-resolving per
  call is also fine — then don't cache), or suppress genuinely
  pre-replication sites with the marker and a reason.  Tests and the
  SPMD collective plane (no wire) are out of scope.

- **MV018 untracked-growth** — a cache/queue/ring added to native
  server/worker state or the Python serve plane WITHOUT a registered
  capacity gauge (docs/observability.md "capacity plane"): bytes held
  outside the table shards are invisible to the fleet capacity scrape,
  so the placement advisor (tools/mvplan.py) and mvtop --capacity plan
  over a fiction.  Python scope: serve-plane library classes whose
  container attribute (or class name) says cache/queue/ring must show
  ``capacity.register_gauge(...)`` evidence.  Native scope: member
  declarations of ``std::deque/map/unordered_map/...`` whose name says
  cache/queue/ring/pending/parked/replica/archive/event must carry a
  ``// capacity: <how it is accounted>`` note (naming its gauge or
  report field) on the declaration or the lines just above.  Exempt a
  genuinely bounded-by-protocol container with
  ``mvlint: MV018-exempt(<why growth is bounded>)`` — the reason is
  mandatory; an empty marker does not suppress.

A file that cannot be linted at all (SyntaxError, undecodable bytes)
is never silently skipped: it gets an explicit **MV000 parse-failure**
finding, so a botched merge cannot hide a file from every other rule.

Suppress a finding with a reasoned marker on the same line:
``mvlint: MV00N-exempt(<why this site is legal>)`` — uniform across
MV001–MV018, Python and native files alike; the reason is mandatory and
an empty marker does not suppress.  The bare legacy form
``# mvlint: disable=MV00N`` still works for tests and one-off triage,
but in-tree code should carry the reasoned form.

``python tools/mvlint.py --changed[=REF]`` lints only the files
``git diff --name-only REF`` reports (default ``HEAD``) — the fast
pre-commit loop on a tree this size; default behavior (full walk) is
unchanged.
"""

from __future__ import annotations

import ast
import os
import re
import sys

# chiprun_stage/ is the unpacked `git archive` the chip smoke is proven
# from (a second copy of the tree, ignored by git), chiprun_out/ what the
# chip tool brings back.
SKIP_DIRS = {".git", "build", "__pycache__", ".claude", "node_modules",
             "chiprun_stage", "chiprun_out", ".jax_cache"}

# Helpers that wrap numpy buffers into ctypes pointers (native binding).
PTR_HELPERS = {"_fp", "_ip"}

# Host-sync markers for MV003.
HOST_SYNC_ATTRS = {"block_until_ready", "device_get", "item"}
HOST_SYNC_NP = {"asarray"}

SUBPROCESS_FNS = {"run", "call", "check_call", "check_output"}


class Finding:
    def __init__(self, path, line, rule, msg):
        self.path, self.line, self.rule, self.msg = path, line, rule, msg

    def __str__(self):
        return f"{self.path}:{self.line}: {self.rule}: {self.msg}"


# Registry of every rule id this linter can emit.  tests/
# test_static_analysis.py's meta test walks this to assert each rule
# has at least one seeded-violation test — add the rule here AND a
# test there, or the suite fails.
RULES = {
    "MV000": "parse-failure",
    "MV001": "ctypes-temporary",
    "MV002": "dangling-async",
    "MV003": "host-sync-in-jit",
    "MV004": "unbounded-subprocess",
    "MV005": "unbounded-retry",
    "MV006": "print-in-library",
    "MV007": "unbounded-client-cache",
    "MV008": "noncontiguous-ctypes",
    "MV009": "blocking-socket-in-reactor",
    "MV010": "observability-bypass",
    "MV011": "per-key-label-cardinality",
    "MV012": "bridge-copy-churn",
    "MV013": "row-at-a-time-table-loop",
    "MV014": "wall-clock-interval",
    "MV015": "swallowed-native-exception",
    "MV016": "serve-read-without-deadline",
    "MV017": "stale-shard-route",
    "MV018": "untracked-growth",
    "MV019": "unbounded-cqe-drain",
}


def _suppressed(finding, lines):
    """True if the finding's source line carries a suppression marker:
    the reasoned ``mvlint: MVxxx-exempt(<reason>)`` form (uniform across
    MV001–MV018, Python and native alike; empty reason does NOT
    suppress) or the bare legacy ``mvlint: disable=MVxxx``."""
    line = (lines[finding.line - 1]
            if 0 < finding.line <= len(lines) else "")
    if f"mvlint: disable={finding.rule}" in line:
        return True
    return bool(re.search(rf"mvlint:\s*{finding.rule}-exempt\(\s*[^)\s]",
                          line))


def _call_name(func):
    """Trailing name of a call target: Name id or Attribute attr."""
    if isinstance(func, ast.Name):
        return func.id
    if isinstance(func, ast.Attribute):
        return func.attr
    return ""


def check_ctypes_temporary(tree, path):
    """MV001: _fp/_ip/ctypes.data_as over anything but a bare name."""
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        # _fp(expr) / _ip(expr): expr must be a Name.
        if (_call_name(node.func) in PTR_HELPERS and node.args
                and not isinstance(node.args[0], ast.Name)):
            out.append(Finding(
                path, node.lineno, "MV001",
                f"{_call_name(node.func)}() over a temporary "
                f"expression — bind the array to a local first so a "
                f"reference keeps the buffer alive across the native "
                f"call"))
        # expr.ctypes.data_as(...): expr must be a Name.
        f = node.func
        if (isinstance(f, ast.Attribute) and f.attr == "data_as"
                and isinstance(f.value, ast.Attribute)
                and f.value.attr == "ctypes"
                and not isinstance(f.value.value, ast.Name)):
            out.append(Finding(
                path, node.lineno, "MV001",
                "ctypes.data_as over a temporary expression — bind the "
                "array to a local first"))
    return out


def check_dangling_async(tree, path):
    """MV002: *_async(...) result discarded as a bare statement."""
    # Exempt `with pytest.raises(...):` bodies — the call is *supposed*
    # to throw before a handle ever exists, so there is nothing to bind.
    exempt = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.With) and any(
                isinstance(item.context_expr, ast.Call)
                and _call_name(item.context_expr.func) == "raises"
                for item in node.items):
            for sub in ast.walk(node):
                exempt.add(id(sub))
    out = []
    for node in ast.walk(tree):
        if (isinstance(node, ast.Expr) and isinstance(node.value, ast.Call)
                and id(node) not in exempt
                and _call_name(node.value.func).endswith("_async")):
            out.append(Finding(
                path, node.lineno, "MV002",
                f"result of {_call_name(node.value.func)}() discarded — "
                f"bind the handle and wait() it (or del it to withdraw "
                f"the in-flight request)"))
    return out


def _is_jit_call(call):
    """True for jax.jit(...) / jit(...) / functools.partial(jax.jit, ...)."""
    name = _call_name(call.func)
    if name == "jit":
        return True
    if name == "partial" and call.args:
        first = call.args[0]
        return isinstance(first, (ast.Name, ast.Attribute)) and \
            _call_name(first) == "jit"
    return False


def check_host_sync_in_jit(tree, path):
    """MV003: host syncs inside jit-traced functions (tables layer)."""
    # Collect jit-traced bodies: decorated defs, defs whose name is
    # passed to a jit call, and lambdas passed to jit directly.
    jitted_names = set()
    jitted_bodies = []
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                is_jit = (_call_name(dec) == "jit"
                          or (isinstance(dec, ast.Call) and _is_jit_call(dec)))
                if is_jit:
                    jitted_bodies.append(node)
                    break
        if isinstance(node, ast.Call) and _is_jit_call(node):
            args = node.args[1:] if _call_name(node.func) == "partial" \
                else node.args
            for a in args:
                if isinstance(a, ast.Name):
                    jitted_names.add(a.id)
                elif isinstance(a, ast.Lambda):
                    jitted_bodies.append(a)
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) and \
                node.name in jitted_names:
            jitted_bodies.append(node)

    out = []
    seen = set()
    for fn in jitted_bodies:
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call) or id(node) in seen:
                continue
            f = node.func
            sync = None
            if isinstance(f, ast.Attribute):
                if (f.attr in HOST_SYNC_NP and isinstance(f.value, ast.Name)
                        and f.value.id in ("np", "numpy")):
                    sync = f"np.{f.attr}"
                elif f.attr in HOST_SYNC_ATTRS:
                    sync = f".{f.attr}()"
            if sync:
                seen.add(id(node))
                out.append(Finding(
                    path, node.lineno, "MV003",
                    f"{sync} inside a jit-traced function — host sync "
                    f"breaks tracing / forces a per-step device flush; "
                    f"hoist it out of the traced body"))
    return out


def check_unbounded_subprocess(tree, path):
    """MV004: bench subprocess calls without a timeout bound."""
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        kwargs = {k.arg for k in node.keywords}
        # subprocess.run / call / check_*(…, timeout=…)
        if (isinstance(f, ast.Attribute) and f.attr in SUBPROCESS_FNS
                and isinstance(f.value, ast.Name)
                and f.value.id == "subprocess" and "timeout" not in kwargs):
            out.append(Finding(
                path, node.lineno, "MV004",
                f"subprocess.{f.attr}() without timeout= — a hung child "
                f"wedges the whole bench run; bound it"))
        # proc.communicate() / proc.wait() without timeout
        if (isinstance(f, ast.Attribute) and f.attr in ("communicate", "wait")
                and "timeout" not in kwargs and not node.args):
            out.append(Finding(
                path, node.lineno, "MV004",
                f".{f.attr}() without timeout= — a hung child wedges the "
                f"whole bench run; bound it"))
    return out


def _walk_same_scope(node):
    """Walk a statement subtree WITHOUT descending into nested function/
    class bodies — a `break` inside a nested def cannot exit this loop."""
    stack = [node]
    while stack:
        n = stack.pop()
        yield n
        for child in ast.iter_child_nodes(n):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.Lambda, ast.ClassDef)):
                continue
            stack.append(child)


def check_unbounded_retry(tree, path):
    """MV005: `while True` + a swallow-all except and no way out."""
    out = []
    for node in ast.walk(tree):
        if not (isinstance(node, ast.While)
                and isinstance(node.test, ast.Constant)
                and node.test.value is True):
            continue
        scope = list(_walk_same_scope(node))
        # Any exit anywhere in the loop bounds it (break / return /
        # re-raise — including inside handlers).
        if any(isinstance(n, (ast.Break, ast.Return, ast.Raise))
               for n in scope):
            continue
        for sub in scope:
            if not isinstance(sub, ast.Try):
                continue
            for handler in sub.handlers:
                broad = handler.type is None or (
                    isinstance(handler.type, ast.Name)
                    and handler.type.id in ("Exception", "BaseException"))
                if broad:
                    out.append(Finding(
                        path, handler.lineno, "MV005",
                        "unbounded retry: `while True` whose broad "
                        "except swallows every failure with no "
                        "break/return/raise — a persistent error spins "
                        "forever; cap attempts + back off "
                        "(fault.RetryPolicy)"))
                    break
    return out


def check_print_in_library(tree, path):
    """MV006: print()/getLogger(__name__) in library code — use Log."""
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        if isinstance(f, ast.Name) and f.id == "print":
            out.append(Finding(
                path, node.lineno, "MV006",
                "print() in library code bypasses the leveled logger "
                "(-log_level/-log_file are ignored and ranks interleave) "
                "— route through multiverso_tpu.log.Log"))
        # logging.getLogger(__name__) / logging.getLogger(): an ad-hoc
        # logger outside the configured multiverso_tpu sink hierarchy.
        if (isinstance(f, ast.Attribute) and f.attr == "getLogger"
                and isinstance(f.value, ast.Name)
                and f.value.id == "logging"):
            anonymous = (not node.args
                         or (isinstance(node.args[0], ast.Name)
                             and node.args[0].id == "__name__"))
            if anonymous:
                out.append(Finding(
                    path, node.lineno, "MV006",
                    "logging.getLogger(__name__) in library code mints a "
                    "logger outside the configured multiverso_tpu sinks "
                    "— route through multiverso_tpu.log.Log"))
    return out


# Identifiers that count as eviction evidence for MV007: a class that
# pops/limits anywhere is treated as managing its own bound.
BOUND_EVIDENCE = {"popitem", "maxlen", "max_entries", "capacity", "evict",
                  "max_size", "popleft"}


def _is_unbounded_container(value):
    """True for `{}` / `dict()` / `OrderedDict()` / `deque()` with no
    maxlen — the constructions MV007 polices."""
    if isinstance(value, ast.Dict) and not value.keys:
        return True
    if not isinstance(value, ast.Call):
        return False
    name = _call_name(value.func)
    if name in ("dict", "OrderedDict", "defaultdict"):
        return not value.args and not value.keywords
    if name == "deque":
        return not any(k.arg == "maxlen" for k in value.keywords) and \
            len(value.args) < 2
    return False


def check_unbounded_client_cache(tree, path):
    """MV007: self.*cache*/self.*queue* dict/deque with no bound."""
    out = []
    for cls in ast.walk(tree):
        if not isinstance(cls, ast.ClassDef):
            continue
        evidence = any(
            (isinstance(n, ast.Attribute) and n.attr in BOUND_EVIDENCE)
            or (isinstance(n, ast.Name) and n.id in BOUND_EVIDENCE)
            or (isinstance(n, ast.keyword) and n.arg in BOUND_EVIDENCE)
            or (isinstance(n, ast.arg) and n.arg in BOUND_EVIDENCE)
            for n in ast.walk(cls))
        if evidence:
            continue
        for node in ast.walk(cls):
            targets = []
            if isinstance(node, ast.Assign):
                targets = node.targets
                value = node.value
            elif isinstance(node, ast.AnnAssign) and node.value is not None:
                targets = [node.target]
                value = node.value
            else:
                continue
            for t in targets:
                if not (isinstance(t, ast.Attribute)
                        and isinstance(t.value, ast.Name)
                        and t.value.id == "self"):
                    continue
                lname = t.attr.lower()
                if "cache" not in lname and "queue" not in lname:
                    continue
                if _is_unbounded_container(value):
                    out.append(Finding(
                        path, node.lineno, "MV007",
                        f"self.{t.attr} is an unbounded client-side "
                        f"cache/queue (dict/deque with no size bound, "
                        f"class has no eviction) — serve-style traffic "
                        f"grows it until OOM; bound it (LRU/maxlen) or "
                        f"annotate why growth is bounded"))
    return out


# Producers whose result is guaranteed C-contiguous for MV008: explicit
# contiguity coercions, fresh-allocation constructors, and the binding's
# own `_f32` (which wraps ascontiguousarray).  `ravel()` always returns
# a contiguous array (copying when needed) — unlike `reshape`/`.T`.
CONTIG_PRODUCERS = {"ascontiguousarray", "_f32", "ravel", "copy",
                    "zeros", "ones", "full", "empty", "arange",
                    "zeros_like", "ones_like", "full_like", "empty_like",
                    "frombuffer", "fromiter",
                    # The binding's out=/borrow= validator: RAISES on a
                    # non-contiguous / wrong-dtype buffer instead of
                    # copying (the host-bridge borrow protocol,
                    # docs/host_bridge.md) — contiguity is proven by the
                    # call having returned.
                    "_contig_f32"}


def check_noncontiguous_ctypes(tree, path):
    """MV008: numpy array → ctypes pointer without a provable
    C-contiguous producer in the same function scope."""
    out = []
    for fn in ast.walk(tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        # The sanctioned pointer helpers themselves wrap a bare
        # parameter — call SITES are what this rule polices.
        if fn.name in PTR_HELPERS:
            continue
        # name -> provably-contiguous? (last assignment wins; walking in
        # source order is close enough for straight-line binding code).
        proven = {}
        for node in ast.walk(fn):
            if not (isinstance(node, ast.Assign)
                    and len(node.targets) == 1
                    and isinstance(node.targets[0], ast.Name)):
                continue
            name = node.targets[0].id
            v = node.value
            if isinstance(v, ast.Call):
                tail = _call_name(v.func)
                if tail in CONTIG_PRODUCERS:
                    proven[name] = True
                elif tail == "asarray" and v.args and not isinstance(
                        v.args[0], ast.Name):
                    # np.asarray over a literal/comprehension constructs
                    # a fresh (contiguous) array; over a Name it may
                    # pass a strided view through unchanged.
                    proven[name] = True
                else:
                    proven[name] = False
            else:
                proven[name] = False
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            arg = None
            how = None
            if (_call_name(node.func) in PTR_HELPERS and node.args
                    and isinstance(node.args[0], ast.Name)):
                arg = node.args[0].id
                how = f"{_call_name(node.func)}({arg})"
            f = node.func
            if (isinstance(f, ast.Attribute) and f.attr == "data_as"
                    and isinstance(f.value, ast.Attribute)
                    and f.value.attr == "ctypes"
                    and isinstance(f.value.value, ast.Name)):
                arg = f.value.value.id
                how = f"{arg}.ctypes.data_as(...)"
            if arg is None or proven.get(arg) is True:
                continue
            out.append(Finding(
                path, node.lineno, "MV008",
                f"{how}: no guaranteed C-contiguous path for '{arg}' in "
                f"this function — a strided view here hands native code "
                f"a mismatched memory layout; route it through "
                f"np.ascontiguousarray (or a fresh constructor) first"))
    return out


# Registry-bypassing metric classes for MV010: direct instantiation
# skips the process-global Registry, so the series is invisible to
# snapshot()/Prometheus/the in-band ops scrape.
METRIC_CLASSES = {"Counter", "Gauge", "Histogram"}


def check_observability_bypass(tree, path):
    """MV010: metric series minted outside the registry, and span ids
    captured but never propagated (library code only)."""
    out = []
    # (a) direct Counter/Gauge/Histogram construction.  Only names
    # provably from the metrics module fire — collections.Counter in
    # unrelated code must not.
    imported = set()
    for node in ast.walk(tree):
        if (isinstance(node, ast.ImportFrom) and node.module
                and node.module.split(".")[-1] == "metrics"):
            for a in node.names:
                if a.name in METRIC_CLASSES:
                    imported.add(a.asname or a.name)
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        direct = (isinstance(f, ast.Name) and f.id in imported)
        attr = (isinstance(f, ast.Attribute) and f.attr in METRIC_CLASSES
                and isinstance(f.value, ast.Name)
                and f.value.id == "metrics")
        if direct or attr:
            name = f.id if direct else f"metrics.{f.attr}"
            out.append(Finding(
                path, node.lineno, "MV010",
                f"{name}(...) mints a series OUTSIDE the unified "
                f"registry — it never reaches snapshot(), the "
                f"Prometheus flush, or the in-band ops scrape; use "
                f"metrics.{(f.attr if attr else f.id).lower()}() "
                f"instead"))
    # (b) `with span(...) as tid:` whose id is never used in the body.
    for node in ast.walk(tree):
        if not isinstance(node, ast.With):
            continue
        for item in node.items:
            ce = item.context_expr
            if not (isinstance(ce, ast.Call)
                    and _call_name(ce.func) == "span"
                    and isinstance(item.optional_vars, ast.Name)):
                continue
            var = item.optional_vars.id
            used = any(isinstance(n, ast.Name) and n.id == var
                       for stmt in node.body for n in ast.walk(stmt))
            if not used:
                out.append(Finding(
                    path, item.context_expr.lineno, "MV010",
                    f"span() binds its trace id to '{var}' but never "
                    f"uses it — the id exists to be PROPAGATED (native "
                    f"set_trace_id, a wire header, a log line); "
                    f"propagate it or drop the `as` clause (nested "
                    f"spans inherit the thread-local id)"))
    return out


# Identifiers that mark a label value as key-derived for MV011.  The
# match is per underscore-separated word, so `table_id`/`rank` stay
# legal (bounded dimensions) while `key`, `row_id`, `hot_row`, `word`,
# `token_id` fire.  "id"/"ids" alone intentionally do NOT fire — every
# bounded handle is an id; the unbounded ones are keys/rows/tokens.
KEYISH_WORDS = {"key", "keys", "row", "rows", "rowid", "word", "words",
                "token", "tokens"}

# Registry accessor names whose labels= MV011 inspects.
REGISTRY_ACCESSORS = {"counter", "gauge", "histogram"}


def _keyish_name(name: str) -> bool:
    return any(w in KEYISH_WORDS for w in name.lower().split("_"))


def _keyish_expr(node) -> "str | None":
    """Terminal identifier of `node`'s expression that names a table
    key/row id, or None.  Walks through str()/format calls, f-strings,
    subscripts and attributes — `str(row_id)`, `f"{key}"`,
    `self.hot_rows[i]` all derive from a key."""
    for sub in ast.walk(node):
        name = None
        if isinstance(sub, ast.Name):
            name = sub.id
        elif isinstance(sub, ast.Attribute):
            name = sub.attr
        elif isinstance(sub, ast.arg):
            name = sub.arg
        if name and _keyish_name(name):
            return name
    return None


def check_label_cardinality(tree, path):
    """MV011: metrics labels= whose value derives from a table key/row
    id — unbounded series; route per-key accounting through a sketch."""
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        is_registry = (
            (isinstance(f, ast.Name) and f.id in REGISTRY_ACCESSORS)
            or (isinstance(f, ast.Attribute)
                and f.attr in REGISTRY_ACCESSORS
                and isinstance(f.value, ast.Name)
                and f.value.id == "metrics"))
        if not is_registry:
            continue
        labels = next((k.value for k in node.keywords
                       if k.arg == "labels"), None)
        if not isinstance(labels, ast.Dict):
            continue
        for key_node, val in zip(labels.keys, labels.values):
            derived = _keyish_expr(val)
            label = (key_node.value
                     if isinstance(key_node, ast.Constant) else "?")
            if derived is None and isinstance(key_node, ast.Constant) \
                    and isinstance(key_node.value, str) \
                    and _keyish_name(key_node.value) \
                    and not isinstance(val, ast.Constant):
                # labels={"key": anything-non-literal}: the label NAME
                # says it's per-key even when the value spelling hides it.
                derived = key_node.value
            if derived is not None:
                out.append(Finding(
                    path, val.lineno, "MV011",
                    f"labels= value for '{label}' derives from "
                    f"'{derived}' — a per-key/row label mints one "
                    f"series per key (unbounded cardinality; the "
                    f"registry cap collapses them into one overflow "
                    f"series).  Per-key accounting goes through a "
                    f"bounded sketch (multiverso_tpu/sketch.py), not "
                    f"registry labels"))
    return out


# ---------------------------------------------------------------- MV012
# The numpy-facing native bridge surface (NativeRuntime + the raw MV_*
# entry points): arguments headed here are on the host-bridge hot path.
BRIDGE_CALLS = {
    "array_add", "array_get", "array_get_async",
    "matrix_add_all", "matrix_get_all",
    "matrix_add_rows", "matrix_get_rows", "matrix_get_rows_async",
    "kv_add", "kv_get",
}
# Inline producers that cost a full payload copy per call.
CHURN_PRODUCERS = {"astype", "copy", "ascontiguousarray"}


def check_bridge_copy_churn(tree, path):
    """MV012: astype/.copy()/ascontiguousarray minted inline on an
    argument of a native bridge add/get call — per-call copy churn the
    arena/borrow protocol exists to kill (docs/host_bridge.md)."""
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        tail = _call_name(node.func)
        is_bridge = tail in BRIDGE_CALLS or (
            tail is not None and tail.startswith("MV_")
            and ("Add" in tail or "Get" in tail))
        if not is_bridge:
            continue
        args = list(node.args) + [k.value for k in node.keywords]
        # One level into the ctypes pointer helpers: `_fp(x.astype(...))`
        # is the same churn wearing a wrapper.
        for a in list(args):
            if isinstance(a, ast.Call) and _call_name(a.func) in PTR_HELPERS:
                args.extend(a.args)
        for arg in args:
            if not isinstance(arg, ast.Call):
                continue
            churn = _call_name(arg.func)
            if churn in CHURN_PRODUCERS:
                out.append(Finding(
                    path, arg.lineno, "MV012",
                    f"{churn}(...) minted inline on an argument of "
                    f"{tail}(...) — a full-payload copy per bridge "
                    f"call; allocate through rt.arena().alloc(...) and "
                    f"pass borrowed=/out= (zero-copy, contiguity by "
                    f"construction), or hoist the conversion out of "
                    f"the hot path (docs/host_bridge.md)"))
    return out


# ---------------------------------------------------------------- MV013
# Table ops whose per-row Python-loop form MV013 flags (a batched
# rows=/keys= spelling exists for every one of them).
ROW_CALLS = {"get_rows", "add_rows", "matrix_get_rows",
             "matrix_add_rows"}
KV_CALLS = {"get", "add"}


def _names_in(node):
    return {n.id for n in ast.walk(node) if isinstance(n, ast.Name)}


def check_row_at_a_time(tree, path):
    """MV013: row-at-a-time table fetch/add inside a ``for`` over ids
    (apps/ and models/ only — the batched call is the whole point of
    the row APIs; docs/embedding.md)."""
    out = []
    for loop in ast.walk(tree):
        if not isinstance(loop, ast.For):
            continue
        targets = _names_in(loop.target)
        if not targets:
            continue
        for node in _walk_same_scope(loop):
            if not isinstance(node, ast.Call):
                continue
            tail = _call_name(node.func)
            args = list(node.args) + [k.value for k in node.keywords]

            def uses_target(a):
                # The loop variable itself, or a 1-element list/tuple
                # literal wrapping it: `t.get_rows([i])`.
                if isinstance(a, ast.Name) and a.id in targets:
                    return True
                if isinstance(a, (ast.List, ast.Tuple)) \
                        and len(a.elts) == 1:
                    e = a.elts[0]
                    return isinstance(e, ast.Name) and e.id in targets
                return False

            fired = False
            if tail in ROW_CALLS and any(uses_target(a) for a in args):
                fired = True
            elif tail in KV_CALLS:
                # kv.get([k]) / kv.add({k: v}): only the unambiguous
                # single-element literal forms (dict.get(k) etc. must
                # not false-positive).
                for a in args:
                    if isinstance(a, (ast.List, ast.Tuple)) \
                            and len(a.elts) == 1 \
                            and isinstance(a.elts[0], ast.Name) \
                            and a.elts[0].id in targets:
                        fired = True
                    if isinstance(a, ast.Dict) and len(a.keys) == 1 \
                            and isinstance(a.keys[0], ast.Name) \
                            and a.keys[0].id in targets:
                        fired = True
            if fired:
                out.append(Finding(
                    path, node.lineno, "MV013",
                    f"row-at-a-time {tail}(...) over loop variable(s) "
                    f"{sorted(targets & (_names_in(node)))} — each "
                    f"iteration pays a full monitor/serve/wire round "
                    f"trip; batch the ids and call {tail} ONCE with "
                    f"the whole rows=/keys= set (docs/embedding.md)"))
    return out


# ---------------------------------------------------------------- MV014
# Non-monotonic clock reads whose DIFFERENCE is an interval.
_WALL_CLOCK_ATTRS = {("time", "time"), ("datetime", "now"),
                     ("datetime", "utcnow")}


def _wall_clock_call(node):
    """True for ``time.time()`` / ``datetime.now()`` /
    ``datetime.utcnow()`` (module- or class-qualified)."""
    if not isinstance(node, ast.Call) or not isinstance(
            node.func, ast.Attribute):
        return False
    base = node.func.value
    base_name = (base.attr if isinstance(base, ast.Attribute)
                 else base.id if isinstance(base, ast.Name) else None)
    return (base_name, node.func.attr) in _WALL_CLOCK_ATTRS


def check_wall_clock_interval(tree, path):
    """MV014: both operands of a subtraction derive from a
    non-monotonic clock read — an interval measured on a clock that
    steps.  Scoped per function (plus the module body), so a
    wall-clock TIMESTAMP that merely rides into arithmetic with a
    monotonic duration (``time.time() - dt``) stays legal."""
    out = []
    scopes = [tree] + [n for n in ast.walk(tree)
                       if isinstance(n, (ast.FunctionDef,
                                         ast.AsyncFunctionDef))]
    for scope in scopes:
        body = scope.body if isinstance(
            scope, (ast.FunctionDef, ast.AsyncFunctionDef)) else [
            n for n in scope.body
            if not isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.ClassDef))]
        derived = set()
        for node in body:
            for sub in ast.walk(node):
                if isinstance(sub, ast.Assign) and _wall_clock_call(
                        sub.value):
                    for tgt in sub.targets:
                        if isinstance(tgt, ast.Name):
                            derived.add(tgt.id)

        def clockish(n):
            return _wall_clock_call(n) or (
                isinstance(n, ast.Name) and n.id in derived)

        for node in body:
            for sub in ast.walk(node):
                if (isinstance(sub, ast.BinOp)
                        and isinstance(sub.op, ast.Sub)
                        and clockish(sub.left) and clockish(sub.right)):
                    out.append(Finding(
                        path, sub.lineno, "MV014",
                        "interval measured with a non-monotonic clock "
                        "(time.time()/datetime.now() minus another "
                        "wall-clock read): NTP steps/DST turn this "
                        "into phantom latency spikes or negative "
                        "durations — use time.monotonic()/"
                        "monotonic_ns()/perf_counter() for durations "
                        "(docs/observability.md latency plane)"))
    return out


# ---------------------------------------------------------------- MV016
# Serve-protocol read types whose requests must carry a deadline stamp.
SERVE_READ_TYPES = {"RequestGet", "RequestVersion", "RequestReplica"}


def check_serve_read_without_deadline(tree, path):
    """MV016: a serve-path read minted without a deadline/QoS stamp —
    the budget-stamping entry points (AnonServeClient / HedgedReader)
    exist so the server can shed a read whose caller already gave up;
    a bare ``pack_frame(MSG["RequestGet"], ...)`` bypasses them."""
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        fn = node.func
        name = fn.id if isinstance(fn, ast.Name) else (
            fn.attr if isinstance(fn, ast.Attribute) else None)
        if name != "pack_frame" or not node.args:
            continue
        first = node.args[0]
        if not (isinstance(first, ast.Subscript)
                and isinstance(first.value, ast.Name)
                and first.value.id == "MSG"):
            continue
        sl = first.slice
        key = (sl.value if isinstance(sl, ast.Constant)
               else getattr(getattr(sl, "value", None), "value", None))
        if key not in SERVE_READ_TYPES:
            continue
        if any(kw.arg == "qos" for kw in node.keywords):
            continue
        out.append(Finding(
            path, node.lineno, "MV016",
            f"serve read {key} minted without a deadline/QoS stamp: "
            "pass qos=(class_id, budget_ns) so the server can drop it "
            "once the caller's budget is blown instead of burning an "
            "apply slot (deadline propagation, docs/serving.md "
            "\"tail\"); suppress only where the unstamped pre-13 "
            "frame is deliberate"))
    return out


# ---------------------------------------------------------------- MV017
# Placement-lookup call names that mint a shard→rank routing decision.
ROUTING_LOOKUPS = {"server_rank", "shard_owner", "owner_of", "OwnerOf",
                   "shard_of", "ShardOf"}
# Names whose presence anywhere in the function counts as an epoch
# re-check (or adoption) — the discipline MV017 enforces.
EPOCH_CHECKS = {"routing_epoch", "note_routing_epoch",
                "_check_routing_epoch"}
# Wire-surface call names a cached route must not be carried across:
# the native-runtime / serve-client / raw-frame read-write entry
# points (SPMD-plane shard math never reaches these).
ROUTE_WIRE_CALLS = {"send_raw", "recv_reply", "get_shard", "get_rows",
                    "get_replica", "table_version", "array_get",
                    "array_add", "matrix_get_rows", "matrix_get_all",
                    "add_rows", "matrix_add_rows", "kv_get", "kv_add"}


def _shardish_name(node) -> bool:
    name = None
    if isinstance(node, ast.Name):
        name = node.id
    elif isinstance(node, ast.Attribute):
        name = node.attr
    return bool(name) and bool(
        re.search(r"(?:^|_)(?:n(?:um)?_?)?(?:servers?|shards?)$", name))


def _routing_decision(node) -> bool:
    """An expression that derives a shard owner: `x % shards`-style
    modulo against a shard/server count, or a placement lookup call."""
    if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Mod):
        return _shardish_name(node.right)
    if isinstance(node, ast.Call):
        return _call_name(node.func) in ROUTING_LOOKUPS
    return False


def check_stale_shard_route(tree, path):
    """MV017: a routing decision cached across wire calls with no
    routing-epoch re-check anywhere in the function."""
    out = []
    for fn in ast.walk(tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        # Any epoch consultation in the function satisfies the rule.
        checked = False
        for node in ast.walk(fn):
            name = None
            if isinstance(node, ast.Name):
                name = node.id
            elif isinstance(node, ast.Attribute):
                name = node.attr
            if name in EPOCH_CHECKS:
                checked = True
                break
        if checked:
            continue
        route_lines = []   # assignments that CACHE a routing decision
        wire_lines = []
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign):
                for sub in ast.walk(node.value):
                    if _routing_decision(sub):
                        route_lines.append(node.lineno)
                        break
            if isinstance(node, ast.Call) and \
                    _call_name(node.func) in ROUTE_WIRE_CALLS:
                wire_lines.append(node.lineno)
        for rl in route_lines:
            if any(wl > rl for wl in wire_lines):
                out.append(Finding(
                    path, rl, "MV017",
                    "table→shard routing decision cached across a wire "
                    "call with no routing-epoch re-check: after a "
                    "failover promotion / elastic join the shard→rank "
                    "map flips (docs/replication.md) and this route "
                    "points at a corpse — consult routing_epoch() in "
                    "this function (or re-resolve per call), or "
                    "suppress a genuinely pre-replication site with a "
                    "reason"))
                break  # one finding per function is enough signal
    return out


# ---------------------------------------------------------------- MV015
# Native/wire/table call evidence: a try block touching any of these is
# on a delivery path whose failures must not vanish into `except: pass`.
NATIVE_WIRE_ATTRS = {
    # raw sockets / framing
    "sendall", "sendmsg", "sendto", "recv", "recv_into", "recvfrom",
    "connect", "send_raw", "recv_reply", "next_frame", "unpack_frame",
    "pack_frame", "ops_report", "get_shard", "get_replica",
    # native runtime bridge + table ops
    "array_add", "array_get", "matrix_add_all", "matrix_get_all",
    "matrix_add_rows", "matrix_get_rows", "kv_add", "kv_get",
    "barrier", "flush_adds", "table_version",
}
# Teardown calls: a try whose ONLY calls are these is the legal
# best-effort-cleanup idiom (close may race a dead peer by design).
CLEANUP_ATTRS = {"close", "shutdown", "unregister", "kill", "remove",
                 "unlink", "terminate"}
_LOG_METHODS = {"debug", "info", "warning", "warn", "error",
                "exception", "fatal", "critical"}


def _is_log_call(node):
    """Log.error(...) / logger.warning(...) / self._log.info(...)."""
    return (isinstance(node, ast.Expr) and isinstance(node.value, ast.Call)
            and isinstance(node.value.func, ast.Attribute)
            and node.value.func.attr in _LOG_METHODS)


def _handler_swallows(handler):
    """True when the except body only passes and/or logs — no raise,
    no return value, no fallback assignment, no flow control."""
    for stmt in handler.body:
        if isinstance(stmt, ast.Pass) or _is_log_call(stmt):
            continue
        return False
    return True


def _try_call_attrs(try_body):
    """Attribute/function names called anywhere in the try body."""
    names = set()
    for stmt in try_body:
        for node in _walk_same_scope(stmt):
            if isinstance(node, ast.Call):
                tail = _call_name(node.func)
                if tail:
                    names.add(tail)
    return names


def check_swallowed_native_exception(tree, path):
    """MV015: `except ...: pass` (or bare log-and-drop) around
    native-call/wire/table code in library scope — the delivery
    failures the audit plane exists to surface, hidden at the source."""
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Try):
            continue
        called = _try_call_attrs(node.body)
        risky = {n for n in called
                 if n in NATIVE_WIRE_ATTRS or n.startswith("MV_")}
        if not risky:
            continue  # teardown-only (close/shutdown/...) never fires
        for handler in node.handlers:
            if not _handler_swallows(handler):
                continue
            out.append(Finding(
                path, handler.lineno, "MV015",
                f"exception around native/wire call(s) "
                f"{sorted(risky)[:4]} swallowed ({'pass' if any(isinstance(s, ast.Pass) for s in handler.body) else 'log-and-drop'}) "
                f"— a dropped send/apply error here is a silently lost "
                f"add, exactly what the delivery-audit plane exists to "
                f"surface (docs/observability.md \"audit plane\"); "
                f"re-raise, return an error, or suppress with the "
                f"marker + a reason if the drop is deliberate"))
    return out


# ---------------------------------------------------------------- MV018
# Untracked growth: containers whose NAME (or owning class name) says
# they hold traffic-shaped state must be visible to the capacity plane
# (docs/observability.md "capacity plane").
_GROWTH_WORDS = ("cache", "queue", "ring")


def _is_container_construction(value):
    """`{}` / dict() / OrderedDict() / defaultdict() / deque(...) —
    bounded or not: MV007 polices the bound, MV018 the VISIBILITY."""
    if isinstance(value, ast.Dict) and not value.keys:
        return True
    if not isinstance(value, ast.Call):
        return False
    return _call_name(value.func) in ("dict", "OrderedDict",
                                      "defaultdict", "deque")


def check_untracked_growth(tree, path):
    """MV018 (Python serve plane): a growth-named container attribute
    in a class with no ``capacity.register_gauge`` evidence."""
    out = []
    for cls in ast.walk(tree):
        if not isinstance(cls, ast.ClassDef):
            continue
        evidence = any(
            (isinstance(n, ast.Attribute) and n.attr == "register_gauge")
            or (isinstance(n, ast.Name) and n.id == "register_gauge")
            for n in ast.walk(cls))
        if evidence:
            continue
        cls_growth = any(w in cls.name.lower() for w in _GROWTH_WORDS)
        for node in ast.walk(cls):
            targets = []
            if isinstance(node, ast.Assign):
                targets, value = node.targets, node.value
            elif isinstance(node, ast.AnnAssign) and node.value is not None:
                targets, value = [node.target], node.value
            else:
                continue
            for t in targets:
                if not (isinstance(t, ast.Attribute)
                        and isinstance(t.value, ast.Name)
                        and t.value.id == "self"):
                    continue
                lname = t.attr.lower()
                named = any(w in lname for w in _GROWTH_WORDS)
                if not (named or cls_growth):
                    continue
                if _is_container_construction(value):
                    out.append(Finding(
                        path, node.lineno, "MV018",
                        f"self.{t.attr} in {cls.name} holds serve-plane "
                        f"state with no registered capacity gauge — the "
                        f"fleet capacity scrape (and tools/mvplan.py) "
                        f"cannot see these bytes; call "
                        f"capacity.register_gauge(...) for the class or "
                        f"mark the line `mvlint: MV018-exempt(reason)` "
                        f"with why growth is bounded elsewhere"))
    return out


# Native member declarations of node-based containers whose name says
# growth.  [^;=] crosses newlines, so multi-line declarations match;
# the reported line is the NAME's line.
_NATIVE_GROWTH = re.compile(
    r"std::(?:deque|list|map|multimap|set|unordered_map|unordered_set)<"
    r"[^;=]*>\s+(\w*(?:cache|queue|ring|pending|parked|replica|archive|"
    r"event|wq)\w*)\s*(?:GUARDED_BY\s*\([^)]*\)\s*)?[;={]")
# Evidence window above the declaration (comment lines).
_MV018_LOOKBACK = 4
_MV018_EXEMPT = re.compile(r"MV018-exempt\(\s*[^)\s]")


def check_native_untracked_growth(path, src):
    """MV018 (native server/worker state): growth-named container
    members need a `// capacity:` accounting note or a reasoned
    exemption marker within the declaration's comment block."""
    out = []
    for m in _NATIVE_GROWTH.finditer(src):
        name_line = src.count("\n", 0, m.start(1)) + 1
        lines = src.splitlines()
        lo = max(0, src.count("\n", 0, m.start()) + 1 - 1 -
                 _MV018_LOOKBACK)
        window = "\n".join(lines[lo:name_line])
        if "capacity:" in window:
            continue
        if _MV018_EXEMPT.search(window):
            continue
        out.append(Finding(
            path, name_line, "MV018",
            f"native member {m.group(1)} is growth-shaped state with "
            f"no capacity accounting note — add `// capacity: <gauge "
            f"or report field>` naming how the bytes reach the "
            f"\"capacity\" report, or `mvlint: MV018-exempt(reason)` "
            f"explaining why growth is bounded"))
    return out


# ---------------------------------------------------------------- MV009
# Native reactor-context lint: the only non-Python rule.  A file opts in
# with this marker (the epoll engine sources carry it); the rule then
# requires every socket op in it to be nonblocking.
REACTOR_MARKER = "mvlint: reactor-context"

# Socket calls a reactor may only issue nonblocking.  recv/send family
# must carry MSG_DONTWAIT in the statement; accept/accept4/connect must
# show SOCK_NONBLOCK (or a same-line suppression with its why).
_SOCKET_CALL = re.compile(
    r"(?<![\w.>])(?:::)?(recv|send|sendmsg|sendto|recvfrom|recvmsg|"
    r"accept4|accept|connect)\s*\(")
_NONBLOCK_EVIDENCE = ("MSG_DONTWAIT", "SOCK_NONBLOCK")
# A blocking call's flags may sit on a continuation line: a statement is
# judged over this many lines starting at the call.
_STMT_LOOKAHEAD = 4


def lint_reactor_file(path, src):
    """MV009 over a marked native source: blocking socket calls."""
    out = []
    lines = src.splitlines()
    for i, line in enumerate(lines):
        code = line.split("//", 1)[0]
        m = _SOCKET_CALL.search(code)
        if not m:
            continue
        # The statement = from the call to its terminating ';' (flags
        # often sit on a continuation line), never past the lookahead —
        # and never into the NEXT statement, whose guard must not vouch
        # for this one.
        stmt = code[m.start():]
        for j in range(i + 1, min(i + _STMT_LOOKAHEAD, len(lines))):
            if ";" in stmt:
                break
            stmt += "\n" + lines[j].split("//", 1)[0]
        stmt = stmt.split(";", 1)[0]
        if any(ev in stmt for ev in _NONBLOCK_EVIDENCE):
            continue
        out.append(Finding(
            path, i + 1, "MV009",
            f"{m.group(1)}() without a nonblocking guard in a "
            f"reactor-context file — one blocking socket call parks "
            f"every connection on this shard; pass MSG_DONTWAIT / use "
            f"SOCK_NONBLOCK (or suppress with the reason if the call "
            f"provably runs off-reactor)"))
    return out


# ---------------------------------------------------------------- MV019
# Bounded completion-queue drains (the io_uring engine's loop
# discipline, docs/transport.md): a `while (true)` / `for (;;)` loop
# that consumes CQEs has no iteration bound, so a peer able to keep the
# completion queue non-empty (multishot ops, a blast of tiny frames)
# starves everything the loop only checks BETWEEN drains — the running_
# flag, watchdog bumps, handoff adoption.  Drains must cap the batch
# (leftover CQEs satisfy the next cycle's wait immediately, so a cap
# costs nothing).
_UNBOUNDED_LOOP = re.compile(
    r"while\s*\(\s*(?:true|1)\s*\)|for\s*\(\s*;\s*;\s*\)")
_CQE_TOUCH = re.compile(r"\bcqes?\b|\bcq_head\b|\bcq_tail\b")
# A drain loop's CQE access sits within its first lines; judging only
# this window keeps an EINTR-retry `while (true)` around a syscall from
# false-positiving on a drain that merely follows it.
_CQE_LOOKAHEAD = 12


def lint_cqe_drain_file(path, src):
    """MV019 over a native source: unbounded CQE-consuming loops."""
    out = []
    lines = src.splitlines()
    for i, line in enumerate(lines):
        code = line.split("//", 1)[0]
        if not _UNBOUNDED_LOOP.search(code):
            continue
        body = "\n".join(
            l.split("//", 1)[0]
            for l in lines[i:min(i + _CQE_LOOKAHEAD, len(lines))])
        if not _CQE_TOUCH.search(body):
            continue
        out.append(Finding(
            path, i + 1, "MV019",
            "unbounded loop consumes completion-queue entries — a peer "
            "that keeps the CQ non-empty starves every check the loop "
            "makes between drains (running_, watchdog, handoffs); cap "
            "the batch (`n < kCqeBatch`-style bound; leftovers satisfy "
            "the next wait immediately) or suppress with "
            "`mvlint: MV019-exempt(reason)` if the bound lives "
            "elsewhere"))
    return out


def lint_native_file(path):
    try:
        with open(path, "r", encoding="utf-8") as fh:
            src = fh.read()
    except (OSError, UnicodeDecodeError) as exc:
        return [Finding(path, 0, "MV000",
                        f"parse-failure: file could not be read "
                        f"({exc.__class__.__name__}: {exc}) — no rule "
                        f"ran over it")]
    findings = []
    if REACTOR_MARKER in src:
        findings += lint_reactor_file(path, src)
    # MV018 runs over every native source: server/worker state is
    # wherever a growth-named member lives.  MV019 likewise — a CQE
    # drain is a CQE drain wherever it appears.
    findings += check_native_untracked_growth(path, src)
    findings += lint_cqe_drain_file(path, src)
    lines = src.splitlines()
    return [f for f in findings if not _suppressed(f, lines)]


NATIVE_EXTS = (".cc", ".cpp", ".cxx", ".h", ".hpp")


def lint_file(path):
    if path.endswith(NATIVE_EXTS):
        return lint_native_file(path)
    try:
        with open(path, "r", encoding="utf-8") as fh:
            src = fh.read()
        tree = ast.parse(src, filename=path)
    except (SyntaxError, UnicodeDecodeError) as exc:
        return [Finding(path, getattr(exc, "lineno", 0) or 0, "MV000",
                        f"parse-failure: file could not be parsed "
                        f"({exc.__class__.__name__}: "
                        f"{getattr(exc, 'msg', None) or exc}) — no "
                        f"rule ran over it; fix the syntax or drop "
                        f"the file from the tree")]
    findings = []
    findings += check_ctypes_temporary(tree, path)
    findings += check_dangling_async(tree, path)
    findings += check_noncontiguous_ctypes(tree, path)
    if f"{os.sep}tables{os.sep}" in path or "/tables/" in path:
        findings += check_host_sync_in_jit(tree, path)
    if os.path.basename(path).startswith("bench"):
        findings += check_unbounded_subprocess(tree, path)
    # Runtime code only: a test may legitimately spin-wait on a child.
    in_tests = (f"{os.sep}tests{os.sep}" in path or "/tests/" in path
                or os.path.basename(path).startswith("test_"))
    if not in_tests:
        findings += check_unbounded_retry(tree, path)
        # MV016: serve reads must carry a deadline stamp — runtime +
        # tools scope (version-tolerance TESTS legitimately mint the
        # pre-13 frame without one).
        findings += check_serve_read_without_deadline(tree, path)
        # MV012: bridge copy churn — runtime code only (tests build
        # ad-hoc arrays, and the seeded-violation suite must be able
        # to spell the violation).
        findings += check_bridge_copy_churn(tree, path)
        # MV017: shard routes cached across wire calls must re-check
        # the routing epoch (docs/replication.md) — runtime + tools +
        # apps scope; tests legitimately pin routes.
        findings += check_stale_shard_route(tree, path)
    # Serve-plane library code: growth must be visible to the capacity
    # plane (MV018) — tests are out of scope (fixtures build throwaway
    # containers on purpose).
    norm = path.replace(os.sep, "/")
    if "/serve/" in norm and not in_tests:
        findings += check_untracked_growth(tree, path)
    # App/model plane: the batched-row-call discipline (the serve/wire
    # layers amortize per CALL, so a per-row Python loop defeats every
    # one of them at once).
    in_apps = any(f"{sep}{d}{sep}" in path.replace(os.sep, "/")
                  for sep in ("/",) for d in ("apps", "models"))
    if in_apps and not in_tests:
        findings += check_row_at_a_time(tree, path)
    # Library code only: apps/ are executable worker scripts whose
    # stdout IS their protocol (NATIVE_LR_OK markers etc.).
    in_library = (("multiverso_tpu" in path)
                  and f"{os.sep}apps{os.sep}" not in path
                  and "/apps/" not in path and not in_tests)
    if in_library:
        findings += check_print_in_library(tree, path)
        findings += check_unbounded_client_cache(tree, path)
        # MV015: swallowed exceptions around native/wire/table calls —
        # library code only (tests legitimately probe failure paths,
        # and the seeded-violation suite must be able to spell one).
        findings += check_swallowed_native_exception(tree, path)
        # MV014: durations on a clock that steps — library code only
        # (a test may freeze/step wall clocks on purpose).
        findings += check_wall_clock_interval(tree, path)
        # metrics.py IS the registry — it legitimately constructs the
        # series classes it registers.
        if os.path.basename(path) != "metrics.py":
            findings += check_observability_bypass(tree, path)
            findings += check_label_cardinality(tree, path)
    # Per-line suppressions: the reasoned -exempt(...) marker (reason
    # mandatory) or the bare legacy disable= form — see _suppressed.
    lines = src.splitlines()
    return [f for f in findings if not _suppressed(f, lines)]


def iter_py_files(paths):
    # Python sources plus the native C++ sources MV009 opts in (only
    # marked files are actually linted — see lint_native_file).
    exts = (".py",) + NATIVE_EXTS
    for p in paths:
        if os.path.isfile(p):
            if p.endswith(exts):
                yield p
            continue
        for root, dirs, files in os.walk(p):
            dirs[:] = [d for d in sorted(dirs) if d not in SKIP_DIRS]
            for name in sorted(files):
                if name.endswith(exts):
                    yield os.path.join(root, name)


def changed_files(root, ref):
    """Lintable files named by ``git diff --name-only REF`` under
    `root` (the --changed pre-commit mode).  Deleted files vanish from
    the diff listing by the time they matter, so only paths that still
    exist are returned."""
    import subprocess
    out = subprocess.run(
        ["git", "-C", root, "diff", "--name-only", "--relative", ref],
        capture_output=True, text=True, timeout=60, check=True)
    files = []
    for rel in out.stdout.splitlines():
        path = os.path.join(root, rel)
        if rel and os.path.isfile(path):
            files.append(path)
    return files


def main(argv):
    args = list(argv)
    changed_ref = None
    for a in list(args):
        if a == "--changed" or a.startswith("--changed="):
            changed_ref = a.partition("=")[2] or "HEAD"
            args.remove(a)
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    paths = args or [repo_root]
    if changed_ref is not None:
        # Lint exactly what the diff names (still honoring extension
        # and SKIP_DIRS filters via iter_py_files on explicit files).
        paths = changed_files(args[0] if args else repo_root, changed_ref)
    findings = []
    nfiles = 0
    for path in iter_py_files(paths):
        nfiles += 1
        findings.extend(lint_file(path))
    for f in findings:
        print(f)
    if findings:
        print(f"mvlint: {len(findings)} finding(s) in {nfiles} file(s)",
              file=sys.stderr)
        return 1
    print(f"mvlint: clean ({nfiles} files)")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
