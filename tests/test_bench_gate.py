"""Tier-1 gate for the bench regression gate itself (ROADMAP item 5;
``make bench-gate`` / tools/bench_compare.py).

Three jobs: the committed BENCH_BASELINE.json must parse and run green
against a line carrying its own values; a seeded regression must fail
loudly (the gate demonstrably fires); and the line-extraction must
survive the messy real formats (driver wrappers, partial lines, a
killed run's unparseable file)."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

sys.path.insert(0, os.path.join(REPO, "tools"))
import bench_compare  # noqa: E402


def _gate(*args):
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "bench_compare.py"),
         *args],
        capture_output=True, text=True, timeout=60)
    return out.returncode, out.stdout + out.stderr


def test_baseline_parses_and_names_real_keys():
    with open(os.path.join(REPO, "BENCH_BASELINE.json")) as fh:
        baseline = json.load(fh)
    assert baseline["keys"], baseline
    for key, spec in baseline["keys"].items():
        assert spec.get("direction") in ("higher", "lower"), key
        assert "value" in spec, key
        assert "band_rel" in spec or "band_abs" in spec, key


def test_gate_green_against_baseline_own_values(tmp_path):
    """A line carrying every baseline key at its committed value sits
    inside every band: the baseline is self-consistent and the gate's
    green path runs over all of its keys."""
    with open(os.path.join(REPO, "BENCH_BASELINE.json")) as fh:
        keys = json.load(fh)["keys"]
    p = tmp_path / "baseline_values.json"
    p.write_text(json.dumps(
        {"extras": {k: spec["value"] for k, spec in keys.items()}}) + "\n")
    rc, out = _gate("--line", str(p))
    assert rc == 0, out
    assert f"{len(keys)} key(s) in band" in out, out
    assert "0 regression(s)" in out, out


def test_gate_without_a_line_says_so():
    """No bench record is committed: with no --line there is nothing to
    gate, and the gate says how to name one instead of passing."""
    rc, out = _gate()
    assert rc == 2, out
    assert "--line" in out and "LINE=" in out, out


def test_gate_fails_on_seeded_regression(tmp_path):
    """A line regressing a gated key out of band must exit nonzero and
    name the key — the 'fails on a seeded regression' acceptance bar."""
    line = {"metric": "x", "value": 1, "unit": "u",
            "extras": {"serve_cached_vs_cold_p50": 4.0,     # floor is 10x
                       "wire_tcp_rtt_ms": 95.0}}            # Nagle is back
    p = tmp_path / "seeded.json"
    p.write_text(json.dumps(line) + "\n")
    rc, out = _gate("--line", str(p))
    assert rc == 1, out
    assert "serve_cached_vs_cold_p50" in out and "FAIL" in out, out
    assert "wire_tcp_rtt_ms" in out, out


def test_gate_passes_in_band_line(tmp_path):
    line = {"extras": {"serve_cached_vs_cold_p50": 25.0,
                       "wire_tcp_rtt_ms": 0.4,
                       "fanin_shed_rate": 0.8,
                       "fanin_accepted": 1000.0,
                       "ops_scrape_p99_ms": 2.5,
                       "ops_overhead_pct": 0.3}}
    p = tmp_path / "ok.json"
    p.write_text("some log noise\n" + json.dumps(line) + "\n")
    rc, out = _gate("--line", str(p))
    assert rc == 0, out


def test_gate_guards_ops_keys(tmp_path):
    """bench_ops acceptance bars (docs/observability.md): scrape p99
    past 5 ms or introspection overhead past 1% must fail the gate."""
    line = {"extras": {"ops_scrape_p99_ms": 9.0,     # > 5 ms bar
                       "ops_overhead_pct": 2.5}}     # > 1% bar
    p = tmp_path / "ops_regressed.json"
    p.write_text(json.dumps(line) + "\n")
    rc, out = _gate("--line", str(p))
    assert rc == 1, out
    assert "ops_scrape_p99_ms" in out and "FAIL" in out, out
    assert "ops_overhead_pct" in out, out


def test_gate_guards_tail_keys(tmp_path):
    """bench_tail acceptance bars (docs/serving.md "tail"): gold
    residency p99 degrading into the broken-admission regime when the
    bulk herd arrives (QoS isolation lost — e.g. the lost-wakeup
    regression read 50x+), a zero hedge-win rate under the seeded
    straggler (hedge path dead), zero deadline sheds (propagation
    broken), or stamp overhead past its band must all fail the gate."""
    line = {"extras": {"tail_qos_isolation": 60.0,     # broken-gate regime
                       "tail_hedge_win_rate": 0.0,     # hedge never won
                       "tail_deadline_shed": 0.0,      # nothing shed
                       "tail_overhead_pct": 6.0}}      # way past band
    p = tmp_path / "tail_regressed.json"
    p.write_text(json.dumps(line) + "\n")
    rc, out = _gate("--line", str(p))
    assert rc == 1, out
    assert "tail_qos_isolation" in out and "FAIL" in out, out
    assert "tail_hedge_win_rate" in out, out
    assert "tail_deadline_shed" in out, out
    assert "tail_overhead_pct" in out, out


def test_gate_passes_in_band_tail_line(tmp_path):
    line = {"extras": {"tail_qos_isolation": 20.0,
                       "tail_hedge_win_rate": 0.8,
                       "tail_deadline_shed": 20.0,
                       "tail_gold_p999_ms": 4.0,
                       "tail_bulk_p999_ms": 400.0,
                       "tail_overhead_pct": 1.5}}
    p = tmp_path / "tail_ok.json"
    p.write_text(json.dumps(line) + "\n")
    rc, out = _gate("--line", str(p))
    assert rc == 0, out


def test_gate_guards_latency_keys(tmp_path):
    """bench_latency acceptance bars (docs/observability.md "latency
    plane"): profiler overhead past the always-on 1% bar, a stage sum
    that stopped telescoping to the end-to-end latency (lost stamps /
    bad clock offsets), or trail overhead past its band must all fail
    the gate."""
    line = {"extras": {"latency_profiler_overhead_pct": 3.0,   # > 1 bar
                       "latency_stage_sum_ratio": 0.5,         # lost stages
                       "latency_timing_overhead_pct": 8.0}}    # way past
    p = tmp_path / "latency_regressed.json"
    p.write_text(json.dumps(line) + "\n")
    rc, out = _gate("--line", str(p))
    assert rc == 1, out
    assert "latency_profiler_overhead_pct" in out and "FAIL" in out, out
    assert "latency_stage_sum_ratio" in out, out
    assert "latency_timing_overhead_pct" in out, out


def test_gate_passes_in_band_latency_line(tmp_path):
    line = {"extras": {"latency_profiler_overhead_pct": 0.4,
                       "latency_timing_overhead_pct": 1.0,
                       "latency_stage_sum_ratio": 0.98,
                       "latency_e2e_p99_ms": 2.0}}
    p = tmp_path / "latency_ok.json"
    p.write_text(json.dumps(line) + "\n")
    rc, out = _gate("--line", str(p))
    assert rc == 0, out


def test_gate_guards_skew_keys(tmp_path):
    """bench_skew acceptance bars (docs/observability.md, workload
    plane): a collapsed zipf skew ratio (the sketches stopped seeing the
    imbalance), a planted hot key missing from the top-K, or sketch
    overhead past the noise band must all fail the gate."""
    line = {"extras": {"skew_ratio_zipf": 2.0,          # < 3.5 floor
                       "skew_hot_recall": 0.6,          # missed hot keys
                       "hotkey_track_overhead_pct": 25.0}}  # way past band
    p = tmp_path / "skew_regressed.json"
    p.write_text(json.dumps(line) + "\n")
    rc, out = _gate("--line", str(p))
    assert rc == 1, out
    assert "skew_ratio_zipf" in out and "FAIL" in out, out
    assert "skew_hot_recall" in out, out
    assert "hotkey_track_overhead_pct" in out, out


def test_gate_passes_in_band_skew_line(tmp_path):
    line = {"extras": {"skew_ratio_zipf": 8.5,
                       "skew_ratio_uniform": 1.3,
                       "skew_hot_recall": 1.0,
                       "hotkey_track_overhead_pct": 1.1}}
    p = tmp_path / "skew_ok.json"
    p.write_text(json.dumps(line) + "\n")
    rc, out = _gate("--line", str(p))
    assert rc == 0, out


def test_gate_guards_bridge_keys(tmp_path):
    """bench_bridge acceptance bars (docs/host_bridge.md): the borrowed
    add/out= get bandwidth collapsing back toward the pre-arena rates,
    the borrow-vs-copy speedup evaporating, or double buffering hiding
    none of the round trip must all fail the gate."""
    line = {"extras": {"bridge_add_host_gbps": 0.2,    # ~the old 0.12
                       "bridge_get_host_gbps": 0.05,
                       "bridge_borrow_speedup": 1.0,   # borrow buys nothing
                       "offload_overlap_pct": 5.0}}    # overlap gone
    p = tmp_path / "bridge_regressed.json"
    p.write_text(json.dumps(line) + "\n")
    rc, out = _gate("--line", str(p))
    assert rc == 1, out
    assert "bridge_add_host_gbps" in out and "FAIL" in out, out
    assert "bridge_get_host_gbps" in out, out
    assert "bridge_borrow_speedup" in out, out
    assert "offload_overlap_pct" in out, out


def test_gate_passes_in_band_bridge_line(tmp_path):
    line = {"extras": {"bridge_add_host_gbps": 2.8,
                       "bridge_get_host_gbps": 0.9,
                       "bridge_borrow_speedup": 3.1,
                       "offload_overlap_pct": 55.0}}
    p = tmp_path / "bridge_ok.json"
    p.write_text(json.dumps(line) + "\n")
    rc, out = _gate("--line", str(p))
    assert rc == 0, out


def test_gate_guards_embedding_keys(tmp_path):
    """bench_embedding acceptance bars (docs/embedding.md, schema 14):
    the row-cache speedup collapsing under its 10x floor, the replica
    p50 falling behind the row-cached p50, the borrowed AddRows
    speedup evaporating (a later codec/staging change silently
    re-copying), the replica push no longer covering the hot head, or
    the sparse reply codec losing its byte saving must all fail."""
    line = {"extras": {"embedding_rowcache_vs_cold_p50": 6.0,   # < 10
                       "embedding_replica_vs_rowcache_p50": 0.7,
                       "embedding_addrows_borrow_speedup": 1.2,  # < 2
                       "embedding_replica_hit_rate": 0.2,
                       "embedding_sparse_bytes_ratio": 1.1}}
    p = tmp_path / "embedding_regressed.json"
    p.write_text(json.dumps(line) + "\n")
    rc, out = _gate("--line", str(p))
    assert rc == 1, out
    assert "embedding_rowcache_vs_cold_p50" in out and "FAIL" in out, out
    assert "embedding_replica_vs_rowcache_p50" in out, out
    assert "embedding_addrows_borrow_speedup" in out, out
    assert "embedding_replica_hit_rate" in out, out
    assert "embedding_sparse_bytes_ratio" in out, out


def test_gate_passes_in_band_embedding_line(tmp_path):
    line = {"extras": {"embedding_rowcache_vs_cold_p50": 11.5,
                       "embedding_replica_vs_rowcache_p50": 1.3,
                       "embedding_addrows_borrow_speedup": 5.0,
                       "embedding_replica_hit_rate": 0.9,
                       "embedding_sparse_bytes_ratio": 5.5}}
    p = tmp_path / "embedding_ok.json"
    p.write_text(json.dumps(line) + "\n")
    rc, out = _gate("--line", str(p))
    assert rc == 0, out


def test_gate_guards_audit_keys(tmp_path):
    """bench_audit acceptance bars (docs/observability.md "audit
    plane"): audit overhead past the always-on 1% bar, a detect
    latency past 50 ms (the books stopped seeing dups promptly), or
    the injected dup never surfacing at all must all fail the gate."""
    line = {"extras": {"audit_overhead_pct": 2.5,        # > 1% bar
                       "audit_add_overhead_pct": 9.0,    # way past band
                       "audit_detect_ms": 400.0,         # dup went dark
                       "audit_dup_named": 0.0}}          # never surfaced
    p = tmp_path / "audit_regressed.json"
    p.write_text(json.dumps(line) + "\n")
    rc, out = _gate("--line", str(p))
    assert rc == 1, out
    assert "audit_overhead_pct" in out and "FAIL" in out, out
    assert "audit_add_overhead_pct" in out, out
    assert "audit_detect_ms" in out, out
    assert "audit_dup_named" in out, out


def test_gate_passes_in_band_audit_line(tmp_path):
    line = {"extras": {"audit_overhead_pct": 0.3,
                       "audit_add_overhead_pct": 1.5,
                       "audit_detect_ms": 0.5,
                       "audit_dup_named": 1.0}}
    p = tmp_path / "audit_ok.json"
    p.write_text(json.dumps(line) + "\n")
    rc, out = _gate("--line", str(p))
    assert rc == 0, out


def test_gate_guards_capacity_keys(tmp_path):
    """bench_capacity acceptance bars (docs/observability.md "capacity
    plane"): accounting overhead past the always-on 1% bar, resident-
    byte books drifting under the ground truth (the advisor would plan
    over a fiction), or a placement proposal whose projected spread
    blows the 2x bar must all fail the gate."""
    line = {"extras": {"capacity_overhead_pct": 3.0,      # > 1% bar
                       "capacity_bytes_accuracy": 0.5,    # lost bytes
                       "capacity_kv_accuracy": 0.4,       # resync broke
                       "mvplan_spread_after": 4.0}}       # > 2x bar
    p = tmp_path / "capacity_regressed.json"
    p.write_text(json.dumps(line) + "\n")
    rc, out = _gate("--line", str(p))
    assert rc == 1, out
    assert "capacity_overhead_pct" in out and "FAIL" in out, out
    assert "capacity_bytes_accuracy" in out, out
    assert "capacity_kv_accuracy" in out, out
    assert "mvplan_spread_after" in out, out


def test_gate_passes_in_band_capacity_line(tmp_path):
    line = {"extras": {"capacity_overhead_pct": 0.4,
                       "capacity_bytes_accuracy": 1.0,
                       "capacity_kv_accuracy": 0.98,
                       "mvplan_spread_after": 1.1}}
    p = tmp_path / "capacity_ok.json"
    p.write_text(json.dumps(line) + "\n")
    rc, out = _gate("--line", str(p))
    assert rc == 0, out


def test_gate_guards_failover_keys(tmp_path):
    """bench_failover acceptance bars (docs/replication.md): detection
    or promotion drifting past seconds, a caller-visible blackout past
    the rpc-deadline+lease bound, ANY lost acked add (zero tolerance —
    the sync-replication contract), or replication read overhead past
    the 3% bar must all fail the gate."""
    line = {"extras": {"failover_detect_ms": 9000.0,      # lease blind
                       "failover_promote_ms": 12000.0,    # stuck epoch
                       "failover_p99_blip_ms": 30000.0,   # outage
                       "failover_lost_acked_adds": 1.0,   # THE violation
                       "repl_overhead_pct": 8.0}}         # > 3% bar
    p = tmp_path / "failover_regressed.json"
    p.write_text(json.dumps(line) + "\n")
    rc, out = _gate("--line", str(p))
    assert rc == 1, out
    assert "failover_detect_ms" in out and "FAIL" in out, out
    assert "failover_promote_ms" in out, out
    assert "failover_p99_blip_ms" in out, out
    assert "failover_lost_acked_adds" in out, out
    assert "repl_overhead_pct" in out, out


def test_gate_passes_in_band_failover_line(tmp_path):
    line = {"extras": {"failover_detect_ms": 1600.0,
                       "failover_promote_ms": 1700.0,
                       "failover_p99_blip_ms": 1800.0,
                       "failover_lost_acked_adds": 0.0,
                       "repl_overhead_pct": 0.5}}
    p = tmp_path / "failover_ok.json"
    p.write_text(json.dumps(line) + "\n")
    rc, out = _gate("--line", str(p))
    assert rc == 0, out


def test_gate_guards_health_keys(tmp_path):
    """bench_health acceptance bars (docs/observability.md "health
    plane", schema 20): the armed health plane costing the serve tier
    real QPS (the evaluation must stay on the flush thread), a seeded
    fault taking longer than 2 s to page through the flush loop, or
    the alert never firing at all must all fail the gate."""
    line = {"extras": {"health_overhead_pct": 8.0,       # > 1% bar
                       "health_alert_detect_ms": 9000.0,  # loop not closing
                       "health_alert_fired": 0.0}}        # never paged
    p = tmp_path / "health_regressed.json"
    p.write_text(json.dumps(line) + "\n")
    rc, out = _gate("--line", str(p))
    assert rc == 1, out
    assert "health_overhead_pct" in out and "FAIL" in out, out
    assert "health_alert_detect_ms" in out, out
    assert "health_alert_fired" in out, out


def test_gate_passes_in_band_health_line(tmp_path):
    line = {"extras": {"health_overhead_pct": 0.5,
                       "health_probe_qps": 4000.0,
                       "health_alert_detect_ms": 700.0,
                       "health_alert_fired": 1.0}}
    p = tmp_path / "health_ok.json"
    p.write_text(json.dumps(line) + "\n")
    rc, out = _gate("--line", str(p))
    assert rc == 0, out


def test_gate_guards_uring_keys(tmp_path):
    """io_uring engine bars (docs/transport.md): the uring RTT drifting
    into the Nagle-pathology regime, the 64 KiB put-burst rate
    collapsing under the committed floor, or the uring serve tier's
    probe p99 blowing past the herd band must all fail the gate."""
    line = {"extras": {"wire_uring_rtt_ms": 40.0,            # Nagle regime
                       "wire_uring_bytes_per_s": 5.0e7,      # < 0.1 GB/s floor
                       "fanin_uring_p99_ms": 90.0}}          # herd p99 blown
    p = tmp_path / "uring_regressed.json"
    p.write_text(json.dumps(line) + "\n")
    rc, out = _gate("--line", str(p))
    assert rc == 1, out
    assert "wire_uring_rtt_ms" in out and "FAIL" in out, out
    assert "wire_uring_bytes_per_s" in out, out
    assert "fanin_uring_p99_ms" in out, out


def test_gate_passes_in_band_uring_line(tmp_path):
    line = {"extras": {"wire_uring_rtt_ms": 0.2,
                       "wire_uring_bytes_per_s": 1.1e9,
                       "fanin_uring_p99_ms": 2.0}}
    p = tmp_path / "uring_ok.json"
    p.write_text(json.dumps(line) + "\n")
    rc, out = _gate("--line", str(p))
    assert rc == 0, out


def test_gate_skips_absent_uring_keys(tmp_path):
    """Hosts whose kernel fails the capability probe emit NO uring keys
    (bench.py gates the whole arm on MV_UringSupported) — the default
    gate must SKIP them, not fail, so non-uring CI stays green."""
    line = {"extras": {"fanin_accepted": 1000.0,
                       "wire_tcp_rtt_ms": 0.4}}
    p = tmp_path / "no_uring.json"
    p.write_text(json.dumps(line) + "\n")
    rc, out = _gate("--line", str(p))
    assert rc == 0, out
    assert "wire_uring" not in [l.split()[1] for l in out.splitlines()
                                if l.startswith("FAIL")], out


def test_last_parseable_line_wins(tmp_path):
    """Schema-7 cumulative emission: the LAST line is the freshest
    cumulative state and must shadow earlier partials."""
    stale = {"extras": {"serve_cached_vs_cold_p50": 2.0}}
    fresh = {"extras": {"serve_cached_vs_cold_p50": 26.0}}
    p = tmp_path / "cumulative.json"
    p.write_text(json.dumps(stale) + "\n" + json.dumps(fresh) + "\n")
    rc, out = _gate("--line", str(p))
    assert rc == 0, out


def test_driver_wrapper_and_null_parse_forms(tmp_path):
    """BENCH_r*.json driver wrappers resolve through `parsed` (or the
    raw `tail`); a parsed=null rc=124 file yields nothing."""
    wrapped = {"n": 9, "rc": 0,
               "parsed": {"extras": {"fanin_accepted": 1000.0}}}
    p = tmp_path / "wrap.json"
    p.write_text(json.dumps(wrapped))
    assert bench_compare.load_line(str(p)) == {"fanin_accepted": 1000.0}
    dead = tmp_path / "dead.json"
    dead.write_text(json.dumps({"n": 5, "rc": 124, "parsed": None,
                                "tail": "WARNING: nothing\n"}))
    assert bench_compare.load_line(str(dead)) is None


def test_strict_mode_fails_on_missing_keys(tmp_path):
    p = tmp_path / "thin.json"
    p.write_text(json.dumps({"extras": {"fanin_accepted": 1000.0}}))
    rc, out = _gate("--line", str(p))
    assert rc == 0, out                      # default: skip
    rc, out = _gate("--line", str(p), "--strict")
    assert rc == 1, out                      # strict: miss = fail
