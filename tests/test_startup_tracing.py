"""A job's start under the program's own names (docs/observability.md,
"Start-up"): the Dashboard monitors around the host-side draw, the
placement and the router's settling, and the compile account that
``compile_cache.configure()`` keeps from JAX's monitoring events."""

import os
import subprocess
import sys
import time

import jax
import jax.monitoring
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from multiverso_tpu import compile_cache, dashboard, metrics, tracing
from multiverso_tpu.models import TransformerConfig, TransformerTrainer

CFG = TransformerConfig(vocab_size=64, dim=32, n_layers=2, n_heads=2,
                        hidden=64, max_seq=32, scan_layers=True)
ROUTED = TransformerConfig(
    vocab_size=96, dim=32, n_layers=2, n_heads=4, hidden=16, max_seq=64,
    num_experts=8, top_k=3, router_scoring="sigmoid",
    router_bias_rate=0.001, moe_dispatch="grouped", aux_loss_coef=0.0)
# monitor -> observations one construction leaves
TRAINER_MONITORS = {"Transformer::init_draw": 1, "Transformer::init_place": 1}
SGNS_MONITORS = {"SkipGram::init_draw": 1, "MatrixTable::init_place": 2}
BALANCE = "Transformer::balance_router_bias"
JAX_MONITORS = ("jax::trace", "jax::lower", "jax::compile_or_load")


def _mesh():
    return Mesh(np.asarray(jax.devices()[:1]), ("dp",))


def _trainer(cfg=CFG):
    return TransformerTrainer(cfg, _mesh(), updater_type="sgd")


def _skipgram(mv):
    from multiverso_tpu.apps import SkipGram

    mv.init(updater_type="sgd")
    return SkipGram(vocab_size=64, dim=8, window=3, negatives=2,
                    learning_rate=0.1, name="startup_w2v")


def _balance():
    trainer = _trainer(ROUTED)
    tokens = np.random.RandomState(0).randint(
        ROUTED.vocab_size, size=(2, 32)).astype(np.int32)
    trainer.balance_router_bias(tokens, 3)
    return trainer


def _fresh_jit(name: str):
    """A jitted function no test has compiled: its name is its program's."""
    def fun(x):
        return jnp.tanh(x) * 3 + 1
    fun.__name__ = fun.__qualname__ = name
    return jax.jit(fun)


@pytest.fixture
def clean():
    """Tracing off and empty, the Dashboard empty, before and after; the
    compile account listening, as after ``init()``."""
    compile_cache.configure()
    tracing.disable()
    tracing.clear()
    dashboard.reset()
    yield
    tracing.disable()
    tracing.clear()
    dashboard.reset()


def _spans(name):
    return [e for e in tracing.events() if e.name == name]


def _inside(inner, t0_us, t1_us):
    return t0_us <= inner.ts_us and inner.ts_us + inner.dur_us <= t1_us + 1


def _constructed_under_tracing(build):
    tracing.enable(rank=0)
    t0 = int(time.time() * 1e6)
    built = build()
    return built, t0, int(time.time() * 1e6)


# ------------------------------------------------- (a) the two constructors
@pytest.mark.parametrize("name", sorted(TRAINER_MONITORS))
def test_trainer_construction_leaves_its_monitor_and_span(clean, name):
    _, t0, t1 = _constructed_under_tracing(_trainer)
    m = dashboard.get_monitor(name)
    assert m.count == TRAINER_MONITORS[name] and m.total_s > 0
    found = _spans(name)
    assert len(found) == m.count
    assert all(_inside(e, t0, t1) for e in found)
    # the draw ends before the placement begins
    (draw,) = _spans("Transformer::init_draw")
    (place,) = _spans("Transformer::init_place")
    assert draw.ts_us + draw.dur_us <= place.ts_us + 1


@pytest.mark.parametrize("name", sorted(SGNS_MONITORS))
def test_skipgram_construction_leaves_its_monitor_and_span(clean, mv, name):
    _, t0, t1 = _constructed_under_tracing(lambda: _skipgram(mv))
    m = dashboard.get_monitor(name)
    assert m.count == SGNS_MONITORS[name] and m.total_s > 0
    found = _spans(name)
    assert len(found) == m.count
    assert all(_inside(e, t0, t1) for e in found)
    if name == "MatrixTable::init_place":
        assert [e.args for e in found] == [{"rows": 64, "stored_cols": 8}] * 2


# ------------------------------------------------------ (b) the bias rule
def test_balance_router_bias_observes_once(clean):
    tracing.enable(rank=0)
    trainer = _balance()
    m = dashboard.get_monitor(BALANCE)
    assert m.count == 1 and m.total_s > 0
    (span,) = _spans(BALANCE)
    assert span.args == {"steps": 3}
    assert 0 < trainer.router_bias_absmax() <= 0.003001
    # the rule's own program is compiled inside it, under its trace id
    inside = [e for e in _spans("jax::compile_or_load")
              if _inside(e, span.ts_us, span.ts_us + span.dur_us)]
    assert inside and all(e.trace_id == span.trace_id for e in inside)
    with pytest.raises(ValueError, match="no router bias"):
        _trainer().balance_router_bias(np.zeros((2, 8), np.int32), 1)
    assert dashboard.get_monitor(BALANCE).count == 1


# -------------------------------------------------- (c) the compile account
def test_configure_twice_installs_one_listener():
    compile_cache.configure()
    compile_cache.configure()
    from jax._src import monitoring

    assert monitoring.get_event_duration_listeners().count(
        compile_cache._on_duration) == 1
    assert monitoring.get_event_listeners().count(
        compile_cache._on_event) == 1


@pytest.mark.parametrize("monitor", JAX_MONITORS)
def test_fresh_jit_is_booked_under_its_name(clean, monitor):
    name = "startup_probe_" + monitor.split("::")[1]
    tracing.enable(rank=0)
    _fresh_jit(name)(np.ones(4, np.float32))
    mine = compile_cache.account()["by_fun"][name]
    assert mine["programs"] == 1
    assert mine["trace_s"] > 0 and mine["lower_s"] > 0
    assert mine["compile_or_load_s"] > 0
    assert dashboard.get_monitor(monitor).count >= 1
    assert [e.args["fun"] for e in _spans(monitor)
            if e.args["fun"] == name] == [name]


def test_other_events_pass_the_listener_by(clean):
    before = compile_cache.account()
    jax.monitoring.record_event("/jax/some/other/event")
    jax.monitoring.record_event_duration_secs("/jax/some/other/duration",
                                              1.5, fun_name="other")
    assert compile_cache.account() == before
    assert dashboard.report(log=False) == {}


def test_second_compile_from_the_persistent_cache_counts_a_hit(
        clean, tmp_path):
    from jax.experimental.compilation_cache import compilation_cache as cc

    saved = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    hits = metrics.counter("compile.cache", {"result": "hit"})
    misses = metrics.counter("compile.cache", {"result": "miss"})
    try:
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        cc.reset_cache()
        h0, m0 = hits.value, misses.value
        tracing.enable(rank=0)
        name = "startup_probe_cached"
        x = np.ones(4, np.float32)
        _fresh_jit(name)(x)
        first = compile_cache.account()["by_fun"][name]
        assert (first["programs"], first["hits"], first["misses"]) == (1, 0,
                                                                       1)
        assert (hits.value - h0, misses.value - m0) == (0, 1)
        jax.clear_caches()
        _fresh_jit(name)(x)
        second = compile_cache.account()["by_fun"][name]
        assert (second["programs"], second["hits"],
                second["misses"]) == (2, 1, 1)
        assert (hits.value - h0, misses.value - m0) == (1, 1)
        assert second["cache_load_s"] > 0
        assert second["compile_or_load_s"] >= second["cache_load_s"]
        assert dashboard.get_monitor("jax::cache_load").count == 1
        assert [e.args.get("cache") for e in _spans("jax::compile_or_load")
                if e.args["fun"] == name] == ["miss", "hit"]
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        cc.reset_cache()


def test_shutdown_report_names_the_slowest_programs(clean, mv, capfd):
    mv.init(args=["-log_level=info"])
    _fresh_jit("startup_probe_reported")(np.ones(4, np.float32))
    by_fun = compile_cache.account()["by_fun"]
    assert by_fun["startup_probe_reported"]["programs"] == 1
    slowest = max(by_fun, key=lambda f: by_fun[f]["compile_or_load_s"])
    mv.shutdown()
    err = capfd.readouterr().err
    assert "compile account:" in err
    assert f"  {slowest}: x{by_fun[slowest]['programs']:d} " in err
    listed = [ln for ln in err.splitlines() if " compile_or_load " in ln]
    assert 1 <= len(listed) - 1 <= compile_cache.REPORTED_PROGRAMS
    assert "jax::compile_or_load" in err            # the Dashboard's table


# ------------------------------------------------ (d) tracing off: no span
@pytest.mark.parametrize("run", ["trainer", "skipgram", "balance", "jit"])
def test_tracing_off_nothing_is_buffered(clean, mv, run):
    {"trainer": _trainer, "skipgram": lambda: _skipgram(mv),
     "balance": _balance,
     "jit": lambda: _fresh_jit("startup_probe_off")(np.ones(3))}[run]()
    assert tracing.events() == []
    ran = {"trainer": "Transformer::init_place",
           "skipgram": "MatrixTable::init_place", "balance": BALANCE,
           "jit": "jax::compile_or_load"}[run]
    assert dashboard.get_monitor(ran).count >= 1      # the timers still ran


# ----------------------------------------------------- the Dashboard's part
def test_monitor_hands_its_args_to_the_span_and_observe_books(clean):
    tracing.enable(rank=0)
    with dashboard.monitor("Probe::section", rows=3):
        pass
    dashboard.get_monitor("Probe::section").observe(0.25)
    m = dashboard.get_monitor("Probe::section")
    assert m.count == 2 and 0.25 <= m.total_s < 0.3 and m.max_s == 0.25
    (span,) = _spans("Probe::section")              # observe leaves no span
    assert span.args == {"rows": 3}


def test_what_shutdown_resets_stays_readable(clean, mv):
    _skipgram(mv)
    assert "MatrixTable::init_place" not in dashboard.ended()
    mv.shutdown()
    assert dashboard.report(log=False) == {}
    ended = dashboard.ended()
    assert ended["MatrixTable::init_place"].count == 2
    assert ended["SkipGram::init_draw"].total_s > 0
    assert "MatrixTable::init_place" not in metrics.snapshot()
    dashboard.reset()                       # the next reset lets them go
    assert dashboard.ended() == {}


def test_import_books_itself_once():
    out = subprocess.run(
        [sys.executable, "-c",
         "import jax, multiverso_tpu as mv\n"
         "m = mv.dashboard.get_monitor('mv::import')\n"
         "print(m.count, m.total_s > 0, mv.tracing.events() == [])"],
        capture_output=True, text=True, timeout=300,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert out.stdout.split() == ["1", "True", "True"], out.stderr
