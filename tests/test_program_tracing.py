"""The program's own names on a trace (docs/observability.md, "chip plane"):
``tracing.span`` reaches both the in-memory buffer and a ``jax.profiler``
session, the ``mv.*`` spans sit where the host work happens, and the
compiled steps carry the scopes the benchmark's per-layer metrics read."""

import contextlib
import functools
import glob
import os
import re
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from multiverso_tpu import tracing
from multiverso_tpu.models import TransformerConfig, TransformerTrainer
from multiverso_tpu.models.transformer import init_params, lm_loss

CFG = TransformerConfig(vocab_size=64, dim=32, n_layers=2, n_heads=2,
                        hidden=64, max_seq=32, scan_layers=True, remat=True,
                        remat_policy="dots")
TRAINER_STEPS = 3
SGNS_SPANS = ("mv.input.next", "mv.input.place", "mv.sgns.dispatch",
              "mv.sgns.sync", "mv.sgns.epoch", "mv.sgns.expand")
SGNS_TOKENS = 300
TRAINER_SPANS = ("mv.trainer.place", "mv.trainer.dispatch")


def _tokens(seed=0, shape=(2, 16)):
    return np.random.RandomState(seed).randint(
        CFG.vocab_size, size=shape).astype(np.int32)


def _trainer():
    return TransformerTrainer(
        CFG, Mesh(np.asarray(jax.devices()[:1]), ("dp",)),
        updater_type="sgd")


def _run_trainer():
    tr = _trainer()
    for _ in range(TRAINER_STEPS):
        loss = tr.train_step_async(_tokens())
    return float(loss)


def _run_sgns():
    """One toy fused epoch; returns the steps it took and the buffer as it
    stood before ``shutdown`` (which clears it)."""
    import multiverso_tpu as mv
    from multiverso_tpu.apps import SkipGram, synthetic_corpus

    mv.config.reset()
    if mv.initialized():
        mv.shutdown()
    mv.init(updater_type="sgd")
    try:
        sg = SkipGram(vocab_size=64, dim=8, window=3, negatives=2,
                      learning_rate=0.1, name="traced_w2v")
        steps, _ = sg.train_epoch_fused(
            synthetic_corpus(SGNS_TOKENS, 64, seed=1), batch_size=128, seed=1)
        return steps, tracing.events()
    finally:
        mv.shutdown()
        mv.config.reset()


@pytest.fixture
def clean_tracing():
    tracing.disable()
    tracing.clear()
    yield
    tracing.disable()
    tracing.clear()


# ------------------------------------------------ (a) the in-memory buffer
@pytest.fixture(scope="module")
def buffered():
    """Both toy loops under ``tracing.enable()``: the events and the step
    count of the fused epoch."""
    tracing.disable()
    tracing.clear()
    tracing.enable(rank=0)
    try:
        steps, events = _run_sgns()
        _run_trainer()
        return events + tracing.events(), steps
    finally:
        tracing.disable()
        tracing.clear()


def _named(events, name):
    return [e for e in events if e.name == name]


@pytest.mark.parametrize("name", SGNS_SPANS)
def test_fused_epoch_leaves_its_spans(buffered, name):
    events, steps = buffered
    assert steps >= 2
    # One pull a step and the pull that finds the batcher dry; one
    # placement and one dispatch a step; one sync and one epoch a call;
    # one expansion a block of the batcher's tokens.
    from multiverso_tpu.apps import word2vec

    want = {"mv.input.next": steps + 1, "mv.input.place": steps,
            "mv.sgns.dispatch": steps, "mv.sgns.sync": 1,
            "mv.sgns.epoch": 1,
            "mv.sgns.expand": -(-SGNS_TOKENS // word2vec._EXPAND_TOKENS),
            }[name]
    found = _named(events, name)
    assert len(found) == want
    (epoch,) = _named(events, "mv.sgns.epoch")
    for e in found:         # nested: inside the epoch, under its trace id
        assert e.trace_id == epoch.trace_id != 0
        assert epoch.ts_us <= e.ts_us
        assert e.ts_us + e.dur_us <= epoch.ts_us + epoch.dur_us + 1
    if name == "mv.sgns.dispatch":
        assert [e.args["step"] for e in found] == list(range(steps))
    if name == "mv.sgns.expand":
        assert sum(e.args["tokens"] for e in found) == SGNS_TOKENS


@pytest.mark.parametrize("name", TRAINER_SPANS)
def test_train_step_async_leaves_its_spans(buffered, name):
    found = _named(buffered[0], name)
    assert len(found) == TRAINER_STEPS
    if name == "mv.trainer.dispatch":
        assert [e.args["step"] for e in found] == list(range(TRAINER_STEPS))
        places = _named(buffered[0], "mv.trainer.place")
        assert all(p.ts_us <= d.ts_us for p, d in zip(places, found))


@pytest.mark.parametrize("run", [_run_sgns, _run_trainer],
                         ids=["sgns", "trainer"])
def test_disabled_the_buffer_stays_empty(clean_tracing, run):
    out = run()
    assert tracing.events() == []
    if run is _run_sgns:
        assert out[0] >= 2 and out[1] == []


def test_monitor_runs_under_a_span_either_way(clean_tracing):
    from multiverso_tpu import dashboard

    with dashboard.monitor("Test::bridged") as m:
        pass
    assert m.count >= 1 and tracing.events() == []
    tracing.enable(rank=0)
    with dashboard.monitor("Test::bridged"):
        pass
    assert [e.name for e in tracing.events()] == ["Test::bridged"]


# --------------------------------------------- (b) the profiler's host line
@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    """Both toy loops under a ``jax.profiler`` session, tracing otherwise
    off: the names on ``/host:CPU``'s ``python`` line, and the buffer."""
    log_dir = str(tmp_path_factory.mktemp("profile"))
    tracing.disable()
    tracing.clear()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=options)
    try:
        _run_sgns()
        _run_trainer()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(log_dir + "/plugins/profile/*/*.xplane.pb")
    data = jax.profiler.ProfileData.from_file(path)
    events = [e for plane in data.planes if plane.name == "/host:CPU"
              for line in plane.lines if line.name == "python"
              for e in line.events]
    return events, tracing.events()


@pytest.mark.parametrize("name", SGNS_SPANS + TRAINER_SPANS)
def test_spans_reach_the_profiler(profiled, name):
    events, buffer = profiled
    assert buffer == []
    found = [e for e in events if e.name == name]
    assert found and all(e.duration_ns > 0 for e in found)
    if name.endswith(".dispatch"):
        steps = [dict(e.stats)["step"] for e in found]
        assert steps == list(range(len(steps)))


# ------------------------------------------ (c) scopes in the compiled text
def _op_names(text):
    return set(re.findall(r'op_name="([^"]*)"', text))


def _holds(op_names, scope):
    """Some ``op_name`` has ``scope`` as a whole path component, bare or
    wrapped in transformations (``transpose(jvp(attn))``)."""
    part = re.compile(rf"(?:^|[/(]){re.escape(scope)}(?:[/)]|$)")
    return any(part.search(n) for n in op_names)


@pytest.fixture(scope="module")
def trainer_text():
    return _op_names(_trainer().lowered_step(_tokens()).compile().as_text())


@pytest.mark.parametrize("scope", ["embed", "layers", "attn", "mlp", "head",
                                   "loss", "update", "rematted_computation"])
def test_trainer_step_holds_scope(trainer_text, scope):
    assert _holds(trainer_text, scope)
    assert not _holds(trainer_text, scope + "x")


def test_trainer_step_marks_the_backward(trainer_text):
    assert any("transpose(jvp(" in n for n in trainer_text)
    # The update is no part of the differentiated function.
    assert not any("jvp(update)" in n for n in trainer_text)


@pytest.fixture(scope="module")
def sgns_text():
    import multiverso_tpu as mv
    from multiverso_tpu.apps import SkipGram

    mv.config.reset()
    if mv.initialized():
        mv.shutdown()
    mv.init(updater_type="sgd")
    try:
        sg = SkipGram(vocab_size=64, dim=8, negatives=2, name="scoped_w2v")
        step, place = sg.make_fused_step()
        ids = place(np.zeros(16, np.int32))
        text = step.lower(*sg.table_in.raw_value(), *sg.table_out.raw_value(),
                          ids, ids, place(np.zeros((16, 2), np.int32))
                          ).compile().as_text()
    finally:
        mv.shutdown()
        mv.config.reset()
    return _op_names(text)


@pytest.mark.parametrize("scope", ["tables.gather", "sgns.grad",
                                   "tables.scatter_apply"])
def test_fused_sgns_step_holds_scope(sgns_text, scope):
    assert _holds(sgns_text, scope)


@pytest.fixture(scope="module")
def flash_texts():
    """The compiled gradient's ``op_name``s on each path of the backward:
    the one fused call the shapes take, and the dq and dkv kernels that run
    where a head's whole-sequence accumulators pass the fused form's
    budget."""
    import importlib

    fa = importlib.import_module("multiverso_tpu.ops.flash_attention")
    q = jnp.ones((1, 1, 128, 32), jnp.float32)

    def loss(q, k, v):
        return fa.flash_attention(q, k, v, interpret=True).sum()

    def text():
        return _op_names(jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            q, q, q).compile().as_text())

    texts = {"fused": text()}
    budget = fa._FUSED_RESIDENT_BYTES
    fa._FUSED_RESIDENT_BYTES = -1
    try:
        texts["split"] = text()
    finally:
        fa._FUSED_RESIDENT_BYTES = budget
    return texts


@pytest.mark.parametrize("path,kernel", [
    ("fused", "flash_fwd"), ("fused", "flash_bwd"),
    ("split", "flash_bwd_dq"), ("split", "flash_bwd_dkv")])
def test_flash_path_holds_kernel_name(flash_texts, path, kernel):
    assert _holds(flash_texts[path], kernel)
    if path == "fused":
        assert not _holds(flash_texts[path], "flash_bwd_dq")
        assert not _holds(flash_texts[path], "flash_bwd_dkv")


# ------------------------------------------- (d) scopes are metadata only
# lm_loss of CFG on init_params(seed=0) and _tokens() (CPU, f32).  From the
# commit before the scopes went in it was 4.451268196105957; PR 46 drew the
# weights anew (on the device, from a seeded key), the program unchanged.
LOSS_BEFORE_SCOPES = 4.708159923553467


def _toy(attn, ffn="dense", **over):
    """One layer of kind ``attn`` with a dense or a routed FFN at toy widths,
    64 tokens (the least the kernels' dispatch takes), every kind's own sizes
    given."""
    model = dict(
        vocab_size=64, dim=32, n_layers=1, n_heads=2, head_dim=16, hidden=16,
        max_seq=64, layer_types=[attn], mlp_layer_types=[ffn],
        sliding_window=8, kv_lora_rank=12, qk_nope_dim=8, qk_rope_dim=4,
        v_head_dim=8, eva_window=32, eva_chunk=4, scan_layers=True,
        remat=True, remat_policy="full")
    if ffn == "sparse":
        model.update(num_experts=4, top_k=2, moe_dispatch="grouped")
    model.update(over)
    return TransformerConfig(**model)


@pytest.mark.parametrize("config", ["dense", "linear", "latent"])
@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_loss_with_scopes_is_the_loss_without(monkeypatch, remat, config):
    # the kinds' kernels in interpret mode, so that their scopes go too
    monkeypatch.setenv("MVTPU_FORCE_FLASH", "1")
    base = {"dense": CFG, "linear": _toy("linear_attention"),
            "latent": _toy("latent_attention", q_lora_rank=12)}[config]
    cfg = replace(base, remat=remat)
    params = jax.tree_util.tree_map(jnp.asarray, init_params(cfg, seed=0))
    toks = jnp.asarray(_tokens(shape=(2, 16) if config == "dense"
                               else (1, 64)))
    with_scopes = jax.value_and_grad(lm_loss)(params, toks, cfg)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    without = jax.value_and_grad(lm_loss)(params, toks, cfg)
    assert float(with_scopes[0]) == float(without[0])
    for a, b in zip(jax.tree_util.tree_leaves(with_scopes[1]),
                    jax.tree_util.tree_leaves(without[1])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    if config == "dense":
        np.testing.assert_allclose(float(with_scopes[0]), LOSS_BEFORE_SCOPES,
                                   rtol=1e-6)


# ------------------------------------------- (e) a sub-layer's parts (PR 50)
# ``attn.proj`` / ``attn.elem`` / ``attn.out`` around an attention kind's
# kernels, ``mlp.up`` / ``mlp.down`` in a dense FFN: read on the chip by
# ``benchmarks/trace/parts.py``, whose rule (``booked``) is the one held here.
ATTN_PARTS = ("attn.proj", "attn.elem", "attn.out")
MLP_PARTS = ("mlp.up", "mlp.down")
PART_CASES = {
    # no ``layer_types``: the parts stand directly in ``attn``
    "untyped": lambda: replace(CFG, max_seq=64, remat_policy="full"),
    "full": lambda: _toy("full_attention"),
    "sliding": lambda: _toy("sliding_attention"),
    "nope": lambda: _toy("full_attention_nope"),
    "latent_q": lambda: _toy("latent_attention", q_lora_rank=12),
    "latent": lambda: _toy("latent_attention"),
    "linear": lambda: _toy("linear_attention"),
    "eva": lambda: _toy("eva_attention"),
    "full_gated": lambda: _toy("full_attention", attn_gate="per_head"),
    "linear_gated": lambda: _toy("linear_attention", attn_gate="per_head"),
    "qk_norm": lambda: _toy("full_attention", qk_norm=True),
    "hc": lambda: _toy("latent_attention", hc_mult=2, hc_sinkhorn_iters=2),
    "routed": lambda: _toy("full_attention", "sparse"),
}
@functools.lru_cache(maxsize=None)
def _part_text(case):
    """The ``op_name``s of the case's compiled gradient, kernels in interpret
    mode (a kind's jnp path has no kernel's name to stand under); compiled
    once a process."""
    cfg = PART_CASES[case]()
    before = os.environ.get("MVTPU_FORCE_FLASH")
    os.environ["MVTPU_FORCE_FLASH"] = "1"
    try:
        return _op_names(
            jax.jit(jax.grad(lambda p, t: lm_loss(p, t, cfg))).lower(
                init_params(cfg, seed=0),
                jnp.asarray(_tokens(shape=(1, 64)))).compile().as_text())
    finally:
        if before is None:
            del os.environ["MVTPU_FORCE_FLASH"]
        else:
            os.environ["MVTPU_FORCE_FLASH"] = before


@pytest.mark.parametrize("part", ATTN_PARTS + MLP_PARTS)
@pytest.mark.parametrize("case", list(PART_CASES))
def test_compiled_step_holds_each_part_that_applies(case, part):
    names = _part_text(case)
    if case == "routed" and part in MLP_PARTS:
        # a routed layer's ``mlp`` stays whole: ``model.moe_*`` read it
        assert not _holds(names, part) and _holds(names, "moe.route")
        return
    assert _holds(names, part)
    inside = "mlp" if part in MLP_PARTS else "attn"
    if case != "untyped" and part in ATTN_PARTS:
        from multiverso_tpu.models.attention import KINDS

        inside += "/" + KINDS[PART_CASES[case]().layout.kinds[0].attn].scope
    held = [n for n in names if f"/{inside}/{part}/" in n]
    assert held, f"not directly inside {inside}"
    assert any("transpose(" in n for n in held), "no backward under it"


@pytest.mark.parametrize("case", list(PART_CASES))
def test_the_parts_close_their_scope(case):
    """No instruction has ``attn``, a kind's scope or a dense layer's ``mlp``
    as the innermost name it knows: it is under a part, a kernel's name or
    ``attn.eva.summarise``."""
    from benchmarks.trace import parts

    open_attn, open_mlp = [], []
    for n in _part_text(case):
        known, under, in_attn, in_mlp = parts.booked(n)
        if in_attn and known is None:
            open_attn.append(n)
        if in_mlp and under != "mlp":
            open_mlp.append(n)
    assert not open_attn
    if case == "routed":
        assert open_mlp
    else:
        assert not open_mlp


@pytest.mark.parametrize("case", list(PART_CASES))
def test_a_kernel_sits_under_no_part(case):
    """A kernel's call keeps the name stack it had: the kind's scope, then the
    kernel's own name, and no part between or inside."""
    from benchmarks.trace import parts, program

    under_kernel = [n for n in _part_text(case)
                    if any(name.startswith(k) for k in parts.KERNELS
                           for name, _ in program.components(n))]
    assert under_kernel, "the kernels' jnp path was traced"
    for n in under_kernel:
        assert not any(name in parts.PARTS
                       for name, _ in program.components(n)), n
