# Repo-level entry points.  The native runtime's build lives in
# multiverso_tpu/native/Makefile; these targets fan out to it plus the
# Python-layer lint (tools/mvlint.py).  docs/static_analysis.md explains
# the analysis layers (analyze / asan / tsan / mvlint).
NATIVE := multiverso_tpu/native
PYTHON ?= python

all:
	$(MAKE) -C $(NATIVE) all

test:
	$(MAKE) -C $(NATIVE) test

# Dynamic sanitizers (unit suite; the multi-process sweeps live in
# tests/test_native.py as test_native_{tsan,asan}_scenarios).
tsan:
	$(MAKE) -C $(NATIVE) tsan

asan:
	$(MAKE) -C $(NATIVE) asan

# Static thread-safety analysis (clang -Werror=thread-safety).
analyze:
	$(MAKE) -C $(NATIVE) analyze

# Repo-specific Python AST lint (ctypes buffer lifetimes, dangling
# async gets, host syncs inside jit, unbounded bench subprocesses).
mvlint:
	$(PYTHON) tools/mvlint.py

# Cross-language contract checker (docs/static_analysis.md): statically
# diffs the wire schema, C-API/ctypes/Lua signatures, rc-code map, and
# the configure.cc/config.py/docs flag surface — no build, no process.
contract:
	$(PYTHON) tools/mvcontract.py --strict

# Umbrella: every static layer.  `make lint` green == what
# tests/test_static_analysis.py + tests/test_contract.py enforce in
# tier-1 (mvlint + mvcontract always; analyze when clang is present).
lint: mvlint contract
	@if command -v clang++ >/dev/null 2>&1; then \
	  $(MAKE) -C $(NATIVE) analyze; \
	else \
	  echo "lint: clang++ not found — skipping make analyze" \
	       "(mvlint ran; install clang for the thread-safety layer)"; \
	fi

# Chaos / fault-injection suite (docs/fault_tolerance.md): native wire
# scenarios (send retry, drop/dup, barrier timeout, heartbeat report,
# injection-off control) + the Python retry/injector/corruption tests,
# under a fixed seed so failures reproduce.
chaos:
	$(MAKE) -C $(NATIVE) all
	MVTPU_FAULT_SEED=1234 JAX_PLATFORMS=cpu \
	  $(PYTHON) -m pytest tests/test_fault.py -q -p no:cacheprovider

# Observability smoke (docs/observability.md): a 2-process native
# session with tracing on — bridges every Dashboard monitor via one
# MV_DumpMonitors call, merges per-rank Chrome traces, and asserts a
# worker Get span correlates with the remote server apply by trace id.
metrics-demo:
	$(MAKE) -C $(NATIVE) all
	JAX_PLATFORMS=cpu $(PYTHON) tools/metrics_demo.py

# Hot-path serve smoke (docs/serving.md): a 2-process wire session
# proving (a) 8 concurrent gets coalesce into <= 2 round trips, (b)
# repeat reads in the staleness bound are served with ZERO wire
# messages, (c) -server_inflight_max=1 sheds retry and converge with
# no lost adds under injected wire chaos.
serve-demo:
	$(MAKE) -C $(NATIVE) all
	JAX_PLATFORMS=cpu $(PYTHON) tools/serve_demo.py

# Compressed wire data plane smoke (docs/wire_compression.md): a
# 2-process wire session proving (a) 1bit adds ship >= 3x fewer bytes
# than raw at equal served values (error feedback), (b) >= 4 small
# async adds collapse into one wire message with read-your-writes
# intact, (c) the native byte/message ledger bridges into the metrics
# registry as net.bytes{dir=...}/net.msgs.
wire-demo:
	$(MAKE) -C $(NATIVE) all
	JAX_PLATFORMS=cpu $(PYTHON) tools/wire_demo.py

# Event-driven serve-tier smoke (docs/transport.md): 256 anonymous
# raw-socket clients against a 2-rank epoll fleet — all accepted and
# served over pseudo-rank reply routing, shed-rate > 0 under
# -server_inflight_max=1 overload, and zero lost adds while rank 0's
# blocking adds eat injected fail_send faults (the PR 2 harness).
fanin-demo:
	$(MAKE) -C $(NATIVE) all
	JAX_PLATFORMS=cpu $(PYTHON) tools/fanin_demo.py

# Live introspection smoke (docs/observability.md): a 2-rank fleet +
# anonymous scraper — fleet-scope Prometheus snapshot with per-rank
# labels, an injected barrier timeout dumping blackbox_rank0.json whose
# spans share trace ids with the merged Chrome trace, and a scraped
# histogram-bucket exemplar trace id resolvable in that trace.
ops-demo:
	$(MAKE) -C $(NATIVE) all
	JAX_PLATFORMS=cpu $(PYTHON) tools/ops_demo.py

# Workload observability smoke (docs/observability.md, workload plane):
# a 2-rank fleet + anonymous herd — zipf(1.0) row stream surfaces every
# planted hot key in the top-K sketch with a bucket-load skew ratio
# > 3x the uniform control's, a NaN-poisoned add dumps
# blackbox_rank0.json naming the table, and stamped worker gets leave
# an observed-staleness histogram.
skew-demo:
	$(MAKE) -C $(NATIVE) all
	JAX_PLATFORMS=cpu $(PYTHON) tools/skew_demo.py

# Sparse-embedding serving smoke (docs/embedding.md): a 2-rank sharded
# embedding table under a zipf hot head — the servers' top-K push
# serves replica hits (worker-stub AND anonymous client), a server-side
# add is observed fresh at staleness 0 within one replica lease, the
# row-granular cache beats cold wire lookups outright, and the
# multi-shard borrowed AddRows out-issues the per-rank staging path.
embedding-demo:
	$(MAKE) -C $(NATIVE) all
	JAX_PLATFORMS=cpu $(PYTHON) tools/embedding_demo.py

# Host-bridge smoke (docs/host_bridge.md): borrowed arena adds land
# exactly with mid-flight releases deferred (no use-after-recycle), the
# zero-copy path beats the copying path outright, and a transformer
# trainer whose optimizer state lives on a remote assign-updater table
# via the double-buffered OffloadedState reproduces the in-memory
# baseline's loss trajectory BIT FOR BIT at equal steps.
bridge-demo:
	$(MAKE) -C $(NATIVE) all
	JAX_PLATFORMS=cpu $(PYTHON) tools/bridge_demo.py

# Latency-attribution smoke (docs/observability.md "latency plane"): a
# 2-rank fleet with wire timing + the SIGPROF sampler armed — an
# anonymous timed probe's per-stage breakdown sums to within 10% of its
# end-to-end latency, the fleet report's p99 exemplar resolves in the
# merged Chrome trace beside profile:* flame spans, and with an
# injected apply-path delay fault, tools/latdoctor.py --fleet names
# `apply` (never the wire) as the dominant p99 stage.
latency-demo:
	$(MAKE) -C $(NATIVE) all
	JAX_PLATFORMS=cpu $(PYTHON) tools/latency_demo.py

# Delivery-audit smoke (docs/observability.md "audit plane"): 2-rank
# fleets on BOTH wire engines where blocking adds eat injected
# fail_send faults (retry absorbs — exact value proves zero lost acked
# adds) and exactly two injected dup sends (the auditor names both with
# their seq ranges); a seeded silent server-side discard fires the
# audit_gap blackbox and diffs as a gap + never-acked tail, not a lost
# acked add; and an -audit=false fleet proves unflagged pre-audit
# frames still parse.
audit-demo:
	$(MAKE) -C $(NATIVE) all
	JAX_PLATFORMS=cpu $(PYTHON) tools/audit_demo.py

# Capacity-plane smoke (docs/observability.md "capacity plane"): a
# 3-rank fleet + zipf herd — the fleet capacity scrape shows skewed
# bucket bytes (mined KV buckets) and skewed bucket load (the herd),
# mvplan bin-packs a dry-run rebalance with projected per-shard spread
# <= 2x, a big table + pinned arena buffer landing mid-run move the
# scraped RSS and arena gauges, and the armed/disarmed A/B shows the
# accounting is ~free with books matching ground truth within 10%.
capacity-demo:
	$(MAKE) -C $(NATIVE) all
	JAX_PLATFORMS=cpu $(PYTHON) tools/capacity_demo.py

# Replication/failover smoke (docs/replication.md): a 3-server
# replicated fleet under an anonymous read herd — SIGKILL the middle
# rank, the backup detects the expired lease on its own (symmetric
# watching), promotes inside the lease window, broadcasts the
# routing-epoch flip, CRC beacons on the promoted shard match the
# dead primary's last audited state, survivors converge EXACTLY, and
# mvaudit --settle proves zero lost acked adds.
failover-demo:
	$(MAKE) -C $(NATIVE) all
	JAX_PLATFORMS=cpu $(PYTHON) tools/failover_demo.py

# Closed-loop health-plane smoke (docs/observability.md "health
# plane"): a 2-rank fleet with the stall watchdog + declarative SLO
# rules armed — a quiet fleet keeps mvdoctor --strict green, a seeded
# apply-delay fault fires the latency burn-rate alert FLEET-WIDE within
# two metric flushes, mvdoctor's top finding names the rank AND the
# `apply` stage (hot keys correlated from the workload plane), and
# clearing the fault resolves the alert and re-greens the gate.
doctor-demo:
	$(MAKE) -C $(NATIVE) all
	JAX_PLATFORMS=cpu $(PYTHON) tools/doctor_demo.py

# Demo umbrella: every acceptance smoke in sequence (each target builds
# the native runtime once; later builds are no-ops).
demos: metrics-demo serve-demo wire-demo fanin-demo ops-demo skew-demo \
       embedding-demo bridge-demo latency-demo audit-demo \
       capacity-demo failover-demo doctor-demo

# Continuous gate of the host and native-fleet sections (bench.py; chip
# speed is benchmarks/run.py's): diff ONE named bench JSON line
# (`python bench.py wire_micro > line.json; make bench-gate
# LINE=line.json`) against the committed BENCH_BASELINE.json with
# per-key noise bands; exits nonzero on an out-of-band regression (serve
# p50, wire RTT, codec byte ratio) and 2, saying so,
# when no LINE is given — no bench record is committed to fall back on.
bench-gate:
	$(PYTHON) tools/bench_compare.py $(if $(LINE),--line $(LINE))

clean:
	$(MAKE) -C $(NATIVE) clean

.PHONY: all test tsan asan analyze mvlint contract lint chaos metrics-demo \
        serve-demo wire-demo fanin-demo ops-demo skew-demo \
        embedding-demo bridge-demo latency-demo audit-demo \
        capacity-demo failover-demo doctor-demo demos bench-gate clean
