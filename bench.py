#!/usr/bin/env python
"""Host and native-fleet gate — prints ONE JSON line.

What this file measures runs on any CPU host: the native C++ runtime over
its loopback wire (transport sweep, SSP, the 8-process LR and word2vec
push-pull fleets of BASELINE.md, the serve tier and its fan-in, tail,
ops, latency, audit, failover, skew, capacity, health and embedding
planes) and the host bridge.  ``make bench-gate LINE=<file>`` holds such
a line against ``BENCH_BASELINE.json``; ``python bench.py wire_micro``
runs only the sections whose names contain ``wire_micro``.

Chip speed is not measured here.  It is ``python benchmarks/run.py``
(``BENCHMARK.json``; read in ``PERF.md`` and ``PERF_LEDGER.jsonl``).

Each section runs under its own try/except — a single regression can cost
that section's numbers but never the whole JSON line — and every failure
lands in ``errors``: a non-empty ``errors`` list is exit code 1.  Every
emitted line names ``platform``, ``device_kind`` and ``device_count``,
and every child a section spawns is pinned to the CPU.

Primary metric: the 8-process native-wire LR rate
(``lr_native8_samples_per_sec``).  Extras ride along in the same JSON
object.
"""

from __future__ import annotations

import json
import os
import signal
import sys
import time
import traceback

import numpy as np

# ---------------------------------------------------------------------------
# Wall budget (VERDICT "budget-proof the harness"): the driver gives the
# bench a finite window and may SIGTERM it at the end.  Every inner
# subprocess deadline scales from what REMAINS of the budget instead of
# a hardcoded 600/300 s, and main() traps SIGTERM/timeout to emit the
# partial JSON accumulated so far — a budget kill costs the missing
# sections, never the whole line.
# ---------------------------------------------------------------------------
_T0 = time.monotonic()
_BUDGET_S = float(os.environ.get("MVTPU_BENCH_BUDGET_S", "3300"))


class _BudgetExceeded(Exception):
    """Raised by the SIGTERM handler / budget checks inside main()."""


def _budget_left() -> float:
    return _BUDGET_S - (time.monotonic() - _T0)


# ---------------------------------------------------------------------------
# Incremental emission + per-benchmark latency percentiles.
#
# Round-5 lesson (a run killed at rc=124 parsed to null; pre-round
# record, removed in PR 21): the JSON line printed only at exit, so
# `timeout`'s SIGTERM landing in an unlucky spot
# (or the follow-up SIGKILL) cost the WHOLE trajectory.  Now every
# completed section re-prints the full cumulative line — the last
# parseable stdout line is always the freshest state, no matter how the
# process dies.  Each section's measured iteration times also feed a
# metrics histogram, so the line carries p50/p95/p99 per benchmark
# (docs/observability.md).
# ---------------------------------------------------------------------------
_CURRENT_SECTION = None
# platform / device_kind / device_count as JAX reports them; filled by
# main() once the backend is up, None on the pre-import schema line.
_DEVICE = {"platform": None, "device_kind": None, "device_count": None}
# The run's ``errors`` list: whole-section failures (main) and failures a
# section survived (_soft_fail).  Non-empty at the end is exit code 1.
_ERRORS = []


def _soft_fail(what: str) -> None:
    """Record the exception being handled and carry on with the section:
    the numbers already banked stay, the run still exits 1."""
    exc = sys.exc_info()[1]
    traceback.print_exc()
    _ERRORS.append(f"{what}: {type(exc).__name__}: {exc}")


def _observe_iter(seconds: float) -> None:
    """Feed one measured iteration into the running section's histogram."""
    if _CURRENT_SECTION is not None:
        from multiverso_tpu import metrics

        metrics.histogram(f"bench.{_CURRENT_SECTION}").observe(seconds)


def _section_percentiles(name: str, results: dict,
                         wall_s: float) -> None:
    """Flatten the section's latency percentiles into the results dict
    (section wall time stands in when nothing sampled iterations)."""
    from multiverso_tpu import metrics

    h = metrics.histogram(f"bench.{name}")
    if h.count == 0:
        h.observe(wall_s)
    for q, key in ((0.50, "p50"), (0.95, "p95"), (0.99, "p99")):
        results[f"{name}_{key}_ms"] = h.quantile(q) * 1e3


def _render_line(results: dict, errors: list) -> dict:
    for metric, unit in _PRIMARY:
        if metric in results:
            line = {
                "metric": metric,
                "value": round(results[metric], 1),
                "unit": unit,
                **_DEVICE,
                # The line's shape since schema 5; its ratios were chip
                # rates over these host rates and went with the chip
                # sections (schema 22).
                "vs_baseline": None,
                "extras": {k: round(v, 2) for k, v in results.items()},
            }
            if errors:
                line["errors"] = errors
            return line
    return {"metric": "bench_partial", "value": 0, "unit": "none",
            **_DEVICE, "vs_baseline": None,
            "extras": {k: round(v, 2) for k, v in results.items()},
            "errors": list(errors)}


def _emit(results: dict, errors: list) -> dict:
    """Print the full cumulative JSON line NOW (last line wins)."""
    line = _render_line(results, errors)
    print(json.dumps(line), flush=True)
    return line


def _bounded(cap: float, floor: float = 30.0) -> float:
    """A subprocess timeout: at most ``cap``, at most the remaining wall
    budget, never under ``floor`` (a too-tight bound would turn a
    healthy child into a spurious TimeoutExpired)."""
    return max(floor, min(cap, _budget_left()))


def _time_loop(fn, *, warmup: int = 3, iters: int = 10) -> float:
    """Median wall seconds per call after warmup (host-synced fns only)."""
    for _ in range(warmup):
        fn()
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
        _observe_iter(times[-1])
    return float(np.median(times))


def _spawn_native_workers(script_name: str, procs: int, marker: str,
                          extra_args=(), exempt_ranks=()):
    """Spawn ``procs`` copies of a native-wire worker script over a fresh
    loopback machine file; returns every rank's stdout (raises naming
    the rank that failed).  The low-level half shared by the LR/w2v
    denominators and the serve section."""
    import socket
    import subprocess
    import sys
    import tempfile

    from multiverso_tpu import native as nat

    nat.ensure_built()
    socks = [socket.socket() for _ in range(procs)]
    for s in socks:
        s.bind(("127.0.0.1", 0))
    eps = [f"127.0.0.1:{s.getsockname()[1]}" for s in socks]
    for s in socks:
        s.close()
    mf = os.path.join(tempfile.mkdtemp(prefix="mvtpu_bench_"), "machines")
    with open(mf, "w") as f:
        f.write("\n".join(eps) + "\n")

    worker = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "multiverso_tpu", "apps", script_name)
    env = dict(os.environ)
    # One process per chip: the parent holds it, so EVERY child is pinned
    # to the CPU here (most workers import jax unguarded).
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.path.dirname(worker).rsplit("multiverso_tpu", 1)[0]
    children = [
        subprocess.Popen(
            [sys.executable, worker, mf, str(r), *map(str, extra_args)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=env)
        for r in range(procs)
    ]
    outs = []
    try:
        for p in children:
            outs.append(p.communicate(timeout=_bounded(600))[0])
    finally:
        for p in children:
            if p.poll() is None:
                p.kill()
    for r, (p, out) in enumerate(zip(children, outs)):
        if r in exempt_ranks:
            continue  # a scripted victim (SIGKILLs itself mid-run)
        if p.returncode != 0 or marker not in out:
            raise RuntimeError(
                f"{script_name} worker failed:\n{out[-2000:]}")
        if "platform=" in out and "platform=cpu" not in out:
            raise RuntimeError(
                f"{script_name} rank {r} left the CPU (the parent holds "
                f"the chip):\n{out[-500:]}")
    return outs


def _run_native_workers(script_name: str, procs: int, marker: str,
                        extra_args=()):
    """Max per-rank barrier-to-barrier ``dt=`` window (the job's
    wall-clock) of a native worker fleet — the LR and word2vec
    north-star denominators."""
    import re

    outs = _spawn_native_workers(script_name, procs, marker, extra_args)
    return max(float(re.search(r"dt=([0-9.]+)", out).group(1))
               for out in outs)


def _uring_supported() -> bool:
    """Capability probe for the io_uring engine (docs/transport.md):
    MV_UringSupported walks IORING_REGISTER_PROBE for every opcode the
    reactor needs.  Bench arms gate on it so hosts with old or
    seccomp-restricted kernels skip the ``*_uring_*`` keys instead of
    failing the run (the bench gate skips absent keys)."""
    try:
        from multiverso_tpu import native as nat

        nat.ensure_built()
        return bool(nat.load().MV_UringSupported())
    except Exception:
        return False


def _run_test_ranks(scenario: str, procs: int, extra=()):
    """Spawn ``procs`` ranks of the native test binary on a fresh
    loopback machine file and return their stdouts.  One home for the
    endpoint-probe/spawn/kill-in-finally plumbing the wire and SSP
    sections share (``_run_native_workers`` is its Python-worker
    sibling); raises naming the rank that actually failed."""
    import socket
    import subprocess
    import tempfile

    from multiverso_tpu import native as nat

    nat.ensure_built()
    native_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "multiverso_tpu", "native")
    binary = os.path.join(native_dir, "build", "mvtpu_test")
    subprocess.run(["make", "-C", native_dir, "-j4", "build/mvtpu_test"],
                   check=True, capture_output=True, timeout=_bounded(600))
    socks = [socket.socket() for _ in range(procs)]
    for s in socks:
        s.bind(("127.0.0.1", 0))
    eps = [f"127.0.0.1:{s.getsockname()[1]}" for s in socks]
    for s in socks:
        s.close()
    mf = os.path.join(tempfile.mkdtemp(prefix="mvtpu_bench_"), "machines")
    with open(mf, "w") as f:
        f.write("\n".join(eps) + "\n")
    children = [subprocess.Popen(
        [binary, scenario, mf, str(r), *map(str, extra)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
        for r in range(procs)]
    outs = []
    try:
        for p in children:
            outs.append(p.communicate(timeout=_bounded(300))[0])
    finally:
        # A dead sibling must not leave the others polling forever and
        # skewing every later section's numbers.
        for p in children:
            if p.poll() is None:
                p.kill()
    for r, p in enumerate(children):
        if p.returncode != 0:
            raise RuntimeError(
                f"{scenario} rank {r} failed:\n{outs[r][-1500:]}")
    return outs


def bench_wire_micro():
    """Direct transport microbench (VERDICT r4 action 6): message-size
    sweep (4 KiB → 16 MiB) at the Net layer itself — the `wire_bench`
    scenario of the native test binary, two ranks on loopback, no
    tables/updaters in the path — so a transport regression shows up
    here even when the LR/w2v aggregates still look healthy.  Keys:
    ``wire_tcp_{put,get}_gbps_{4k,64k,1m,16m}`` + ``wire_tcp_rtt_ms``;
    the MPI sweep (``wire_mpi_*``) runs only under mpirun (without a
    launcher two processes cannot form an MPI world — OpenMPI
    singletons each get size 1, and the scenario reports itself
    skipped)."""
    import shutil
    import subprocess

    suffix = {4096: "4k", 65536: "64k", 1048576: "1m", 16777216: "16m"}

    def parse(out, prefix, res):
        for line in out.splitlines():
            if line.startswith("WIRE "):
                _, size, put, get, rtt = line.split()
                sfx = suffix[int(size)]
                res[f"{prefix}_put_gbps_{sfx}"] = float(put)
                res[f"{prefix}_get_gbps_{sfx}"] = float(get)
                res[f"{prefix}_rtt_ms"] = float(rtt)

    res = {}
    outs = _run_test_ranks("wire_bench", 2, ("tcp",))
    parse(outs[0], "wire_tcp", res)

    # Epoll engine sweep (docs/transport.md): the same protocol through
    # the reactor — wire_epoll_{put,get}_gbps_* + wire_epoll_rtt_ms, so
    # a readiness-model regression is visible next to the blocking
    # engine's numbers.
    try:
        outs = _run_test_ranks("wire_bench", 2, ("epoll",))
        parse(outs[0], "wire_epoll", res)
    except Exception:
        _soft_fail("bench_wire_micro epoll sweep")

    # io_uring engine sweep: the registered-buffer zero-copy reactor
    # next to epoll's numbers — wire_uring_{put,get}_gbps_* +
    # wire_uring_rtt_ms, plus the headline wire_uring_bytes_per_s at
    # the 64 KiB frame point (the acceptance bar: >= 1.5x epoll's same
    # point).  Probe-gated: hosts without uring skip these keys.
    if _uring_supported():
        try:
            outs = _run_test_ranks("wire_bench", 2, ("uring",))
            parse(outs[0], "wire_uring", res)
            if "wire_uring_put_gbps_64k" in res:
                res["wire_uring_bytes_per_s"] = \
                    res["wire_uring_put_gbps_64k"] * 1e9
        except Exception:
            _soft_fail("bench_wire_micro uring sweep")

    # --- payload-codec sweep (docs/wire_compression.md) ----------------
    # The same dense-add workload raw vs 1bit through the FULL runtime
    # (tables + actors + wire), bytes measured at the transport ledger
    # (net.bytes.sent): wire_{raw,1bit}_{bytes,msgs}_per_s plus the
    # headline payload-byte ratio (acceptance: >= 3x; ~30x measured).
    try:
        import re

        codec_outs = _run_test_ranks("codec_wire", 2)
        for m in re.finditer(
                r"CODEC (\w+) bytes=(\d+) msgs=(\d+) secs=([0-9.]+)",
                codec_outs[0]):
            name, nbytes, msgs, secs = m.groups()
            secs = max(float(secs), 1e-9)
            res[f"wire_{name}_bytes_per_s"] = float(nbytes) / secs
            res[f"wire_{name}_msgs_per_s"] = float(msgs) / secs
        m = re.search(r"CODEC_RATIO ([0-9.]+)", codec_outs[0])
        if m:
            res["wire_1bit_bytes_ratio"] = float(m.group(1))
    except Exception:
        _soft_fail("bench_wire_micro codec sweep")

    # --- add-aggregation sub-section -----------------------------------
    # adds-per-wire-message collapse ratio from the agg scenario's
    # counters (agg.adds / agg.flush; acceptance: >= 4 in the demo).
    try:
        agg_outs = _run_test_ranks("agg_bench", 2)
        import re

        m = re.search(r"AGG_BENCH adds=(\d+) flushes=(\d+) secs=([0-9.]+)",
                      agg_outs[0])
        if m:
            adds, flushes, secs = (float(m.group(1)), float(m.group(2)),
                                   max(float(m.group(3)), 1e-9))
            res["add_agg_ratio"] = adds / max(flushes, 1.0)
            res["add_agg_adds_per_s"] = adds / secs
    except Exception:
        _soft_fail("bench_wire_micro agg sweep")

    # MPI sweep: only meaningful under a launcher.
    if shutil.which("mpirun"):
        native_dir = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "multiverso_tpu", "native")
        binary = os.path.join(native_dir, "build", "mvtpu_test")
        # A hung MPI job must cost only the wire_mpi_* keys, not the
        # already-measured TCP sweep above.
        try:
            out = subprocess.run(
                ["mpirun", "-n", "2", binary, "wire_bench", "none", "0",
                 "mpi"],
                capture_output=True, text=True, timeout=_bounded(300))
        except subprocess.TimeoutExpired:
            print("bench_wire_micro: mpirun wire sweep timed out; "
                  "keeping TCP keys", file=sys.stderr)
        else:
            if out.returncode == 0:
                parse(out.stdout, "wire_mpi", res)
    return res


def bench_ssp():
    """SSP vs BSP throughput under a jittery straggler (VERDICT r4
    action 7), via the native ``ssp_tput`` scenario: a steady 40 ms/clock
    worker paired with an alternating 0/160 ms straggler.  ``staleness=3``
    absorbs the jitter that ``staleness=0`` pays worst-case every clock;
    locally ~1.9×.  Key: ``ssp_vs_bsp_speedup``."""
    import re

    def run(staleness):
        outs = _run_test_ranks("ssp_tput", 2, (staleness,))
        return int(re.search(r"SSP_TPUT ms=(\d+)", outs[0]).group(1))

    bsp_ms, ssp_ms = run("0"), run("3")
    return {"ssp_vs_bsp_speedup": bsp_ms / ssp_ms}


def _lr_native_loss(procs: int, steps: int, batch: int, codec: str):
    """Mean final LR loss over a native-wire fleet running `codec`
    (lr_native_worker.py prints loss= after the final barrier)."""
    import re

    outs = _spawn_native_workers("lr_native_worker.py", procs,
                                 "NATIVE_LR_OK",
                                 (steps, batch, codec))
    return float(np.mean([
        float(re.search(r"loss=([0-9.]+)", out).group(1))
        for out in outs]))


def bench_lr_native8(procs: int = 8, steps: int = 60, batch: int = 1024):
    """The BASELINE.json north-star denominator (LR half), measured as
    honestly as the empty reference mount allows: LR through the native
    C++ runtime over the TcpNet wire, 8 worker+server processes on this
    host — mechanically the reference's ``mpirun -n 8`` LR job
    (push/pull per batch through a wire into C++ updaters), minus the
    reference binary itself (unbuildable, mount empty rounds 1-4).
    Aggregate samples/s over the max per-rank barrier-to-barrier
    window."""
    wall = _run_native_workers("lr_native_worker.py", procs,
                               "NATIVE_LR_OK", (steps, batch))
    out = {
        "lr_native8_samples_per_sec": procs * steps * batch / wall,
        "lr_native8_procs": float(procs),
    }
    # Codec convergence ledger (docs/wire_compression.md): the SAME job
    # at equal steps on the raw vs the 1bit wire — acceptance is the
    # final losses matching within 5% (error feedback paying back the
    # 32x byte saving).  Smaller fleet: the claim is about the codec,
    # not the throughput.
    try:
        loss_raw = _lr_native_loss(4, 40, 512, "raw")
        loss_1bit = _lr_native_loss(4, 40, 512, "1bit")
        out["lr_native_loss_raw"] = loss_raw
        out["lr_native_loss_1bit"] = loss_1bit
        out["lr_native_1bit_loss_ratio"] = loss_1bit / loss_raw
    except Exception:
        _soft_fail("bench_lr_native8 codec ledger")
    return out


def bench_w2v_native8(procs: int = 8, steps: int = 20, batch: int = 512):
    """The word2vec half of the north-star ledger (VERDICT r4 action 1):
    skip-gram negative sampling over row-sharded 100k×128 MatrixTables
    through the native wire — workers pull only the touched rows
    (``MV_GetAsyncMatrixTableByRows``, double-buffered: the next batch's
    pull is issued right after this batch's delta pushes, so the ordered
    connection serves it post-add and the prefetch A/B runs under the
    same staleness regime as the blocking path), push row deltas back
    through non-blocking adds, the reference's
    distributed-word-embedding mechanism (SURVEY.md §2.36).

    ``w2v_native8_prefetch_speedup`` compares the same job with the
    double-buffer off (blocking gets).  Caveat: on a single-core host
    (this sandbox: nproc=1) the loopback wire IS cpu work, so there is
    no idle to hide the pull in and the ratio sits near 1.0; the
    mechanism itself is proven by the ``async_overlap`` native scenario
    (wire progress during caller idle, tests/test_native.py)."""
    wall = _run_native_workers("w2v_native_worker.py", procs,
                               "NATIVE_W2V_OK", (steps, batch, 1))
    wall_sync = _run_native_workers("w2v_native_worker.py", procs,
                                    "NATIVE_W2V_OK", (steps, batch, 0))
    return {
        "w2v_native8_pairs_per_sec": procs * steps * batch / wall,
        "w2v_native8_procs": float(procs),
        "w2v_native8_prefetch_speedup": wall_sync / wall,
    }


def bench_serve():
    """Hot-path serve layer (docs/serving.md) over the 2-process native
    wire — the multiprocess configuration the acceptance bar names:
    read QPS and p50/p95/p99 for a cold get (cache off, every read pays
    the full round trip), a cached get (versioned client cache + held
    lease: zero wire messages), and an 8-way concurrent get through the
    coalescing window.  ``serve_cached_vs_cold_p50`` is the headline —
    the cached-read p50 speedup over cold (acceptance: >= 10x)."""
    import re

    outs = _spawn_native_workers("serve_bench_worker.py", 2,
                                 "SERVE_BENCH_OK")
    res = {}
    for m in re.finditer(r"(\w+)=([0-9.]+)", outs[0]):
        if m.group(1) != "rank":
            res[f"serve_{m.group(1)}"] = float(m.group(2))
            # The measured per-op latencies feed this section's own
            # schema-7 percentile keys too.
            if m.group(1).endswith("_ms"):
                _observe_iter(float(m.group(2)) * 1e-3)
    if "serve_cold_p50_ms" in res and res.get("serve_cached_p50_ms"):
        res["serve_cached_vs_cold_p50"] = (res["serve_cold_p50_ms"]
                                           / res["serve_cached_p50_ms"])
    return res


def bench_serve_fanin():
    """Serve-tier fan-in (docs/transport.md): 1000 concurrent ANONYMOUS
    client sockets against ONE server rank's epoll reactor — raw-socket
    clients speaking the serve protocol, no rank identity.  Latency
    phase (8-outstanding version probes) gives ``fanin_p50_ms`` /
    ``fanin_p99_ms``; the overload phase (all 1000 fire a Get at once
    under ``-server_inflight_max=8``) gives ``fanin_shed_rate`` — the
    busy fraction the backpressure gate sheds instead of queueing.
    ``fanin_qps`` covers both phases.  Clients and fleet live in
    ``apps/fanin_bench_worker.py``."""
    import re

    outs = _spawn_native_workers("fanin_bench_worker.py", 2,
                                 "FANIN_BENCH_OK", (1000, 8, 0))
    res = {}
    for out in outs:
        for m in re.finditer(r"(\w+)=([0-9.]+)", out):
            if m.group(1) != "rank":
                res[f"fanin_{m.group(1)}"] = float(m.group(2))
                if m.group(1).endswith("_ms"):
                    _observe_iter(float(m.group(2)) * 1e-3)

    # io_uring serve tier: the same 1000-socket herd against the uring
    # reactor's multishot accept + registered-buffer receive path —
    # ``fanin_uring_p99_ms`` is the gate key (probe-gated like the wire
    # sweep; absent on hosts without uring support).
    if _uring_supported():
        try:
            uouts = _spawn_native_workers(
                "fanin_bench_worker.py", 2, "FANIN_BENCH_OK",
                (1000, 8, 0, "", "uring"))
            for out in uouts:
                for m in re.finditer(r"(\w+)=([0-9.]+)", out):
                    if m.group(1) != "rank":
                        res[f"fanin_uring_{m.group(1)}"] = float(m.group(2))
        except Exception:
            _soft_fail("bench_serve_fanin uring arm")
    return res


def bench_tail(nclients: int = 10000):
    """Tail-at-scale serve tier (docs/serving.md "tail"; schema 17):
    a 10k-socket mixed-tenant load (a bulk Get storm paced by the
    ReplyBusy backoff contract + a gold prober in its own process,
    classes declared in the QoS wire stamp) against one epoll reactor
    with per-class weighted admission armed (``-qos_inflight_max=32``,
    ``bulk:1,gold:8``) — degrades to what RLIMIT_NOFILE supports
    instead of dying with EMFILE.  Reports per-class p50/p99/p99.9
    (``tail_gold_p999_ms`` is gold's SERVER RESIDENCY — the trail's
    recv->reply_send span, what admission actually controls;
    ``tail_bulk_p999_ms`` the throttled tenant's served e2e), the QoS
    isolation ratio ``tail_qos_isolation`` (gold residency p99 with
    the bulk herd / without; <2x where the serve tier owns its CPU —
    the committed band encodes the 1-core bench host's scheduler
    noise), ``tail_hedge_win_rate`` (> 0 under a seeded
    ``apply_delay`` straggler: the replica hedge answers at the
    reactor while the primary is stuck behind the sleeping apply),
    ``tail_deadline_shed`` (1 ns-budget gets dropped at dequeue), and
    ``tail_overhead_pct`` (the QoS/deadline stamp's cost on the
    unhedged fast path, pre-packed frames + interleaved best-of-5).
    Herd + fleet live in ``apps/fanin_bench_worker.py`` (mode=tail)."""
    import re
    import resource

    # RLIMIT_NOFILE satellite: raise our own soft limit too (children
    # inherit it as their starting point; they re-raise and degrade
    # with a logged reason when the hard limit cannot cover the herd).
    soft, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
    want = nclients + 512
    if soft < want:
        try:
            resource.setrlimit(resource.RLIMIT_NOFILE,
                               (min(want, hard) if hard > 0 else want,
                                hard))
        except (ValueError, OSError) as exc:
            print(f"bench_tail: setrlimit failed ({exc}); the worker "
                  f"degrades its herd instead", flush=True)
    outs = _spawn_native_workers("fanin_bench_worker.py", 2,
                                 "FANIN_BENCH_OK",
                                 (nclients, 0, 0, "tail"))
    res = {}
    for out in outs:
        for m in re.finditer(r"(\w+)=(-?[0-9.]+)", out):
            key = m.group(1)
            if key == "rank":
                continue
            name = key if key.startswith("tail_") else f"tail_{key}"
            res[name] = float(m.group(2))
            if key.endswith("_ms"):
                _observe_iter(float(m.group(2)) * 1e-3)
    return res


def bench_ops():
    """Live introspection plane (docs/observability.md): in-band
    ``OpsQuery(metrics)`` scrapes measured UNDER the 1k-connection
    fan-in load — ``ops_scrape_p50_ms``/``ops_scrape_p99_ms`` are the
    scrape latencies while 1000 anonymous clients hammer the same
    reactor (acceptance: p99 < 5 ms), and ``ops_overhead_pct`` is the
    serve-probe QPS the live scrape path cost relative to an unscraped
    A/B run of the same phase (acceptance: < 1%).  Fleet + scraper live
    in ``apps/fanin_bench_worker.py`` (mode=ops)."""
    import re

    outs = _spawn_native_workers("fanin_bench_worker.py", 2,
                                 "FANIN_BENCH_OK", (1000, 8, 0, "ops"))
    res = {}
    for out in outs:
        for m in re.finditer(r"(\w+)=([0-9.]+)", out):
            key = m.group(1)
            if key == "rank":
                continue
            name = key if key.startswith("ops_") else f"ops_{key}"
            res[name] = float(m.group(2))
            if key.startswith("ops_") and key.endswith("_ms"):
                _observe_iter(float(m.group(2)) * 1e-3)
    return res


def bench_latency(nclients: int = 1000):
    """Latency-attribution plane (docs/observability.md "latency
    plane"; schema 15): the 1k-socket anonymous fan-in herd probes one
    epoll server rank in three sweeps — untimed baseline, wire-stamped
    (per-stage p50/p99 breakdown reconstructed from the reply timing
    trails: ``latency_stage_{queue,wire_out,mailbox,apply,reactor,
    wire_back}_{p50,p99}_ms`` + ``latency_e2e_*``), then wire-stamped
    with BOTH sampling profilers (native SIGPROF + the Python sampler
    thread) armed in the busy herd process.
    ``latency_profiler_overhead_pct`` is the QPS the always-on profiler
    cost (acceptance: < 1%), ``latency_timing_overhead_pct`` what the
    48-byte trail + stamps cost, and ``latency_stage_sum_ratio`` checks
    the offset-corrected stages telescope back to the end-to-end
    latency (acceptance: >= 0.85).  Herd + fleet live in
    ``apps/fanin_bench_worker.py`` (mode=latency)."""
    import re

    outs = _spawn_native_workers("fanin_bench_worker.py", 2,
                                 "FANIN_BENCH_OK",
                                 (nclients, 8, 0, "latency"))
    res = {}
    for out in outs:
        for m in re.finditer(r"(\w+)=([0-9.]+)", out):
            key = m.group(1)
            if key == "rank":
                continue
            name = key if key.startswith("latency_") else f"latency_{key}"
            res[name] = float(m.group(2))
            if key.endswith("_ms"):
                _observe_iter(float(m.group(2)) * 1e-3)
    return res


def bench_audit(nclients: int = 1000):
    """Delivery-audit plane (docs/observability.md "audit plane";
    schema 16): the ``bench_serve_fanin`` probe herd re-run with
    auditing armed vs disarmed (MV_SetAudit) → ``audit_overhead_pct``
    (what the always-on plane costs the serve tier; acceptance: < 1%),
    the same A/B over an async add stream (the path the seq stamps and
    server books actually ride) → ``audit_add_overhead_pct``, and one
    injected duplicate send polled through the in-band ``"audit"``
    scrape → ``audit_detect_ms`` (dup injected → named, with its seq
    range, by the anomaly ring).  Herd + fleet live in
    ``apps/fanin_bench_worker.py`` (mode=audit)."""
    import re

    outs = _spawn_native_workers("fanin_bench_worker.py", 2,
                                 "FANIN_BENCH_OK",
                                 (nclients, 8, 0, "audit"))
    res = {}
    for out in outs:
        for m in re.finditer(r"(\w+)=(-?[0-9.]+)", out):
            key = m.group(1)
            if key == "rank":
                continue
            name = key if key.startswith("audit_") else f"audit_{key}"
            res[name] = float(m.group(2))
            if key.endswith("_ms") and float(m.group(2)) >= 0:
                _observe_iter(float(m.group(2)) * 1e-3)
    return res


def bench_failover():
    """Shard replication + lease-triggered failover (docs/
    replication.md; schema 18): a 3-rank replicated fleet
    (``-replication_factor=1``, sync forwarding, 400 ms symmetric
    leases) whose middle rank SIGKILLs itself under a live blocking-add
    loop — ``failover_detect_ms`` (blackout start → lease expiry seen
    by a survivor), ``failover_promote_ms`` (→ shard 1 routed at its
    promoted backup), ``failover_p99_blip_ms`` (the widest gap between
    consecutive successful adds: the caller-visible outage, bounded by
    one rpc deadline + the lease window), ``failover_lost_acked_adds``
    (the fleet ``"audit"`` diff with the promoted shard's book
    answering for the dead rank — MUST be 0: sync replication makes
    "acked" mean applied on both replicas), and ``repl_overhead_pct``
    (anonymous read-herd QPS armed vs disarmed, interleaved arms per
    the PR 12 discipline; reads never forward, acceptance < 3%).
    Fleet lives in ``apps/failover_bench_worker.py``; rank 1 is the
    victim and is exempt from the marker check."""
    import re

    outs = _spawn_native_workers("failover_bench_worker.py", 3,
                                 "FAILOVER_BENCH_OK", (),
                                 exempt_ranks=(1,))
    res = {}
    for out in outs:
        for m in re.finditer(r"(\w+)=(-?[0-9.]+)", out):
            key = m.group(1)
            if key in ("rank", "promotions", "applied"):
                continue
            name = key if key.startswith(
                ("failover_", "repl_")) else f"failover_{key}"
            res[name] = float(m.group(2))
            if key.endswith("_ms") and float(m.group(2)) >= 0:
                _observe_iter(float(m.group(2)) * 1e-3)
    return res


def bench_skew(nclients: int = 1000, rows: int = 2048, reqs: int = 2048):
    """Workload observability plane (docs/observability.md): a zipf(1.0)
    vs uniform row-get stream from a 1000-socket anonymous herd against
    one epoll server rank, with the hot-key/load sketches armed —
    ``skew_ratio_zipf`` must sit well above ``skew_ratio_uniform`` (the
    planted heavy hitters all surface in the top-K sketch), and
    ``hotkey_track_overhead_pct`` is the armed-vs-disarmed QPS cost of
    the accounting on the same herd (acceptance: < 2%).  Fleet + herd
    live in ``apps/skew_bench_worker.py``."""
    import re

    outs = _spawn_native_workers("skew_bench_worker.py", 2,
                                 "SKEW_BENCH_OK",
                                 (nclients, rows, reqs))
    res = {}
    for out in outs:
        for m in re.finditer(r"(\w+)=([0-9.]+)", out):
            key = m.group(1)
            if key == "rank":
                continue
            name = key if key.startswith(
                ("skew_", "hotkey_", "hot_")) else f"skew_{key}"
            res[name] = float(m.group(2))
    if {"hot_hits", "hot_expected"} <= res.keys():
        res["skew_hot_recall"] = (res["hot_hits"]
                                  / max(res["hot_expected"], 1.0))
    return res


def bench_capacity(nclients: int = 256, rows: int = 2048,
                   reqs: int = 512):
    """Capacity plane (docs/observability.md "capacity plane"; schema
    19): a 2-rank epoll fleet under a zipf row-get herd + fresh-key KV
    insert stream, with the byte accounting toggled in INTERLEAVED
    armed/disarmed sweeps (the PR 12 one-persistent-herd discipline) →
    ``capacity_overhead_pct`` (what the always-on accounting costs;
    acceptance < 1%), ``capacity_bytes_accuracy`` /
    ``capacity_kv_accuracy`` (fleet-scraped resident bytes over the
    ground-truth walk; within 10% of 1.0 — the re-arm resync covers
    the disarmed sweeps' inserts), and ``mvplan_spread_after`` (the
    placement advisor's projected per-shard weight spread over the
    scraped fleet; acceptance <= 2x).  Fleet + herd live in
    ``apps/capacity_bench_worker.py``."""
    import re

    outs = _spawn_native_workers("capacity_bench_worker.py", 2,
                                 "CAPACITY_BENCH_OK",
                                 (nclients, rows, reqs))
    res = {}
    for out in outs:
        for m in re.finditer(r"(\w+)=(-?[0-9.]+)", out):
            key = m.group(1)
            if key == "rank":
                continue
            name = key if key.startswith(
                ("capacity_", "mvplan_")) else f"capacity_{key}"
            res[name] = float(m.group(2))
    return res


def bench_health(nclients: int = 256):
    """Closed-loop health plane (docs/observability.md "health plane";
    schema 20): the timed serve probe stream re-run with the health
    plane armed (default SLO rule pack evaluating each metrics flush,
    the native watchdog bump, the in-band alerts push) vs disarmed,
    interleaved best-of-3 → ``health_overhead_pct`` (what closed-loop
    watching costs the serve tier; acceptance: < 1%); then a seeded
    25 ms apply-delay fault under a demo-tightened burn-rate rule →
    ``health_alert_detect_ms`` (fault-to-FIRING wall time through the
    real flush loop; acceptance: < 2 s at the 100 ms flush cadence)
    and ``health_alert_fired`` (must be 1).  Fleet + prober live in
    ``apps/fanin_bench_worker.py`` (mode=health)."""
    import re

    outs = _spawn_native_workers("fanin_bench_worker.py", 2,
                                 "FANIN_BENCH_OK",
                                 (nclients, 8, 0, "health"))
    res = {}
    for out in outs:
        for m in re.finditer(r"(\w+)=(-?[0-9.]+)", out):
            key = m.group(1)
            if key == "rank":
                continue
            name = key if key.startswith("health_") else f"health_{key}"
            res[name] = float(m.group(2))
            if key.endswith("_ms") and float(m.group(2)) >= 0:
                _observe_iter(float(m.group(2)) * 1e-3)
    return res


def bench_embedding(rows: int = 1 << 16, reqs: int = 512):
    """Sparse-embedding serving fast path (docs/embedding.md; schema
    14): a 2-rank epoll fleet holding one row-sharded embedding table
    (shard-faithful scaled-down stand-in for the O(10^7)-row
    recommender), measured on an identical zipf-hot-head row-get
    stream at three tiers — ``embedding_cold_p50_ms`` (serve cache
    off: every lookup is a wire round trip), ``embedding_rowcache_*``
    (the row-granular versioned client cache;
    ``embedding_rowcache_vs_cold_p50`` acceptance >= 10x), and
    ``embedding_replica_*`` (the native hot-key replica serving the
    servers' pushed top-K rows in one pinned-buffer native call;
    ``embedding_replica_vs_rowcache_p50`` acceptance >= 1).  Plus the
    full-zipf(1.0) tail (``embedding_zipf_p99_ms``), bytes/lookup for
    cold-tail all-zero rows with the sparse reply codec off/on
    (``embedding_sparse_bytes_ratio``), and the multi-shard
    borrowed-vs-staged AddRows issue-cost A/B
    (``embedding_addrows_borrow_speedup``, acceptance >= 2x — the
    per-rank staging copies the borrowed run-iovec path removes).
    Fleet + driver live in ``apps/embedding_bench_worker.py``."""
    import re

    outs = _spawn_native_workers("embedding_bench_worker.py", 2,
                                 "EMBED_BENCH_OK", (rows, reqs))
    res = {}
    for out in outs:
        for m in re.finditer(r"(\w+)=([0-9.]+)", out):
            key = m.group(1)
            if key == "rank":
                continue
            name = key if key.startswith("embedding_") \
                else f"embedding_{key}"
            res[name] = float(m.group(2))
            if key.endswith("_ms"):
                _observe_iter(float(m.group(2)) * 1e-3)
    return res


def _diff_gbps(bytes_diff: float, t_full: float, t_half: float,
               bytes_full: float) -> float:
    """Two-point-slope GB/s with a conservative fallback: if timing noise
    inverts the pair (t_half >= t_full), report the un-corrected full-size
    rate instead of dividing by ~0 and printing nonsense."""
    dt = t_full - t_half
    if dt <= 0:
        return bytes_full / t_full / 1e9
    return bytes_diff / dt / 1e9


def bench_bridge(size: int = 16 * 1024 * 1024):
    """Host-bridge fast path (docs/host_bridge.md; schema 13).

    - ``add_host_gbps``/``get_host_gbps`` — borrowed arena adds /
      ``out=`` gets on a single-process native runtime (``assign``
      updater), slope-corrected half-vs-full so fixed per-call cost
      cancels.  REDEFINITION at schema 13: through schema 12 these keys
      named the JAX-plane parity path (a chip section, gone at schema
      22); the unqualified names now mean the native host bridge.  Also
      emitted as
      ``bridge_add_host_gbps``/``bridge_get_host_gbps`` — the NEW,
      collision-free names the bench gate pins (old rounds' identically
      named keys measured a different path and must not gate these).
    - ``bridge_add_copy_gbps``/``bridge_borrow_speedup`` — the same adds
      through the copying (non-borrowed) binding path, and the ratio:
      what the zero-copy handoff buys end to end.
    - ``offload_overlap_pct`` — share of the bridge round-trip hidden by
      OffloadedState's double buffering: A/B of N compute+roundtrip
      steps, blocking vs async push + prefetch, normalized by the
      blocking run's bridge share.
    """
    from multiverso_tpu.native import NativeRuntime
    from multiverso_tpu.parallel.offload import OffloadedState

    # -hotkey_enabled=false: this section measures the BRIDGE, not the
    # workload-observability scan (whose armed-vs-disarmed cost has its
    # own A/B in bench_skew); armed, the per-element NaN/L2 health scan
    # dominates large dense assigns.
    rt = NativeRuntime(args=["-updater_type=assign", "-log_level=error",
                             "-hotkey_enabled=false"])
    out = {}
    try:
        half = size // 2
        nbytes = size * 4
        h_full = rt.new_array_table(size)
        h_half = rt.new_array_table(half)
        arena = rt.arena()
        buf = arena.alloc(size)
        buf[:] = 1.0
        dst = arena.alloc(size)

        def add_borrowed_sec(h, n):
            view = buf[:n]

            def once():
                rt.array_add(h, view, sync=True, borrowed=True)
            return _time_loop(once, warmup=1, iters=3)

        sec_full = add_borrowed_sec(h_full, size)
        sec_half = add_borrowed_sec(h_half, half)
        out["add_host_gbps"] = _diff_gbps(nbytes / 2, sec_full, sec_half,
                                          nbytes)

        def get_out_sec(h, n):
            view = dst[:n]

            def once():
                rt.array_get(h, n, out=view)
            return _time_loop(once, warmup=1, iters=3)

        sec_full = get_out_sec(h_full, size)
        sec_half = get_out_sec(h_half, half)
        out["get_host_gbps"] = _diff_gbps(nbytes / 2, sec_full, sec_half,
                                          nbytes)

        # A/B: the copying (pre-arena) binding path on the same table.
        heap = np.ones(size, np.float32)

        def add_copy_sec(h, d):
            def once():
                rt.array_add(h, d, sync=True)
            return _time_loop(once, warmup=1, iters=3)

        sec_copy_full = add_copy_sec(h_full, heap)
        sec_copy_half = add_copy_sec(h_half, heap[:half])
        out["bridge_add_copy_gbps"] = _diff_gbps(
            nbytes / 2, sec_copy_full, sec_copy_half, nbytes)
        out["bridge_borrow_speedup"] = (
            out["add_host_gbps"] / out["bridge_add_copy_gbps"]
            if out["bridge_add_copy_gbps"] > 0 else 0.0)
        # Gate aliases: new names so the perf gate cannot mistake old
        # rounds' JAX-plane keys for this path.
        out["bridge_add_host_gbps"] = out["add_host_gbps"]
        out["bridge_get_host_gbps"] = out["get_host_gbps"]

        # ---- double-buffer overlap (OffloadedState) -------------------
        # The ZeRO-offload step shape: the expensive forward/backward
        # needs NO optimizer state, so the state round trip issued at
        # the END of step i rides under step i+1's compute; only the
        # cheap update consumes it.  The fake step is a SLEEP — the
        # honest stand-in for an accelerator step, which leaves the
        # host idle (a host-side matmul here measures memory-bandwidth
        # contention with the bridge's own memcpys, not overlap).
        flat = size // 8
        off = OffloadedState(rt, flat)
        vec = np.ones(flat, np.float32)
        off.init(vec)
        compute_s = 0.010

        def steps(blocking: bool, n: int = 8) -> float:
            t0 = time.perf_counter()
            for _ in range(n):
                time.sleep(compute_s)          # "device step" (no state)
                # Not a subprocess wait: the bridge wait is bounded by
                # the native -rpc_timeout_ms deadline.
                s = off.wait()  # mvlint: MV004-exempt(bridge wait bounded by the native -rpc_timeout_ms deadline)
                off.push(s, blocking=blocking)  # update + ship
                if not blocking:
                    off.prefetch()
            return (time.perf_counter() - t0) / n

        steps(False, 2)  # warm both paths' buffers
        t_async = steps(False)
        t_sync = steps(True)
        bridge_share = max(t_sync - compute_s, 1e-9)
        out["offload_overlap_pct"] = float(np.clip(
            100.0 * (t_sync - t_async) / bridge_share, 0.0, 100.0))
        out["bridge_step_sync_ms"] = t_sync * 1e3
        out["bridge_step_async_ms"] = t_async * 1e3
        off.close()
        arena.release(buf)
        arena.release(dst)
    finally:
        rt.shutdown()
    return out


_SECTIONS = [bench_lr_native8, bench_w2v_native8,
             bench_wire_micro, bench_ssp, bench_serve, bench_serve_fanin,
             bench_tail,
             bench_ops, bench_latency, bench_audit, bench_failover,
             bench_skew, bench_capacity, bench_health,
             bench_embedding,
             bench_bridge]

_PRIMARY = [
    ("lr_native8_samples_per_sec", "samples/sec"),
    ("w2v_native8_pairs_per_sec", "pairs/sec"),
]


def main() -> None:
    # Schema/partial line FIRST — before any JAX-touching import — so
    # even a backend-init hang killed by `timeout` leaves one parseable
    # line on stdout.  JAX picks the platform (JAX_PLATFORMS or its own
    # default): nothing here pins one and every later line names it.
    # Schema 22: the chip sections went (LR, word2vec, Add/Get,
    # transformer, MoE, long context, LightLDA) and with them every
    # lr_fused_*, w2v_fused_*, *_pushpull_*, add_*/get_*_gbps of the
    # JAX plane, wire_{put,get}_gbps, wire_rtt_ms, transformer_*,
    # roofline_*, matmul_peak_*, moe_*, longctx*, lda_* key and the two
    # *_fused_vs_native8 ratios; the host keys are schema 21's.
    results = {"bench_schema": 22}
    errors = _ERRORS
    _emit(results, errors)

    import jax

    import multiverso_tpu as mv

    mv.init(args=["-log_level=error"], updater_type="sgd")
    dev = jax.devices()[0]
    _DEVICE.update(platform=dev.platform, device_kind=dev.device_kind,
                   device_count=len(jax.devices()))
    # A budget SIGTERM lands mid-section: convert it to an exception so
    # the JSON accumulated so far still prints (the whole point of the
    # one-line contract — a kill costs sections, not the line).  The
    # per-section _emit below is the belt to this suspender: even an
    # uncatchable SIGKILL only costs the in-flight section.
    def on_sigterm(signum, frame):
        raise _BudgetExceeded(f"signal {signum}")

    # Optional section filter: `python bench.py serve` runs only the
    # sections whose function name contains an argv token.
    wanted = [a for a in sys.argv[1:] if not a.startswith("-")]
    sections = [s for s in _SECTIONS
                if not wanted or any(w in s.__name__ for w in wanted)]

    global _CURRENT_SECTION
    prev_sigterm = signal.signal(signal.SIGTERM, on_sigterm)
    try:
        for section in sections:
            name = section.__name__
            if _budget_left() < 90:
                errors.append(f"{name}: skipped "
                              f"({_budget_left():.0f}s of budget left)")
                continue
            _CURRENT_SECTION = name
            t_section = time.monotonic()
            try:
                results.update(section())
                _section_percentiles(name, results,
                                     time.monotonic() - t_section)
            except (_BudgetExceeded, KeyboardInterrupt) as exc:
                errors.append(f"{name}: budget exceeded "
                              f"({exc}); emitting partial results")
                break
            except Exception as exc:  # keep every other section's numbers
                traceback.print_exc()
                errors.append(
                    f"{name}: {type(exc).__name__}: {exc}")
            finally:
                _CURRENT_SECTION = None
                _emit(results, errors)
    finally:
        signal.signal(signal.SIGTERM, prev_sigterm)
    try:
        mv.shutdown()
    except Exception as exc:
        traceback.print_exc()
        errors.append(f"shutdown: {type(exc).__name__}: {exc}")

    line = _emit(results, errors)
    # Any recorded failure is exit 1 (the cumulative line above still
    # printed); so is a full run that lost every headline.  A FILTERED
    # run legitimately lacks the primary metrics.
    if errors or (line["metric"] == "bench_partial" and not wanted):
        sys.exit(1)


if __name__ == "__main__":
    sys.exit(main())
