"""ISSUE 39: one backward roofline a kernel family, over the work a backward
requires, read from all device time under calls whose names begin with the
family's; the walks' knowledge of today's names; Laguna's settling as data."""

import json
import os

import pytest

from benchmarks import flops, flops_laguna, flops_xing, harness
from benchmarks.trace import program as P
from benchmarks.trace import reduce as R

PEAKS = {"bf16_flops_per_s": 1.97e14, "hbm_bytes_per_s": 8.19e11}
CALL = ('%{} = (bf16[4,8]{{1,0}}, f32[4]{{0}}) custom-call(bf16[4,8]{{1,0}} '
        '%q), custom_call_target="tpu_custom_call"')
FUSION = "%fusion.{} = f32[8]{{0}} fusion(f32[8]{{0}} %a), kind=kLoop"
BWD = "jit(step)/transpose(jvp(layers))/while/body/attn/"
MS = 1e6                                     # ns


class Index:
    def __init__(self, op_names):
        self.op_names = op_names

    def op_name(self, event_name):
        return self.op_names.get(R.instruction_name(event_name))


def traced(calls, steps=2):
    """One chip, ``steps`` step programs of 100 ms; ``calls`` are (kernel
    name, ms a step) run once a step under ``attn``, after 10 ms of a matmul
    fusion under ``mlp``."""
    ops, op_names = [], {"fusion.1": "jit(step)/jvp(layers)/while/body/mlp/"
                                     "dot_general"}
    for step in range(steps):
        at = step * 100 * MS
        ops.append(R.Event(FUSION.format(1), at, at + 10 * MS))
        at += 10 * MS
        for k, (name, ms) in enumerate(calls):
            ops.append(R.Event(CALL.format(f"{name}.{k}"), at, at + ms * MS))
            op_names[f"{name}.{k}"] = f"{BWD}{name}/{name}/pallas_call"
            at += ms * MS
    device = R.DeviceLines(ops=ops, modules=[
        R.Event("jit_step(1)", s * 100 * MS, (s * 100 + 90) * MS)
        for s in range(steps)])
    trace = R.Trace(devices={"/device:TPU:0": device},
                    host=[R.Event(R.WINDOW_SPAN, 0.0, steps * 100 * MS)])
    return P.summarize(trace, Index(op_names))


def _facts():
    """What the runners give, at toy shapes, by the benchmark's own counts."""
    dense = 2 * flops.causal_attention_flops(1, 4, 1024, 128)
    laguna = {"n_layers": 2, "n_heads": 8, "n_kv_heads": 2, "head_dim": 128,
              "dim": 1024, "sliding_window": 512,
              "layer_types": ["sliding_attention"] * 2}
    latent = {"n_layers": 2, "n_heads": 4, "qk_nope_dim": 128,
              "qk_rope_dim": 64, "v_head_dim": 128, "mtp_layers": 0}
    return {
        "chips": 1,
        "attention_flops_per_step": dense,
        "attention_bwd_bytes_per_step":
            2 * flops.flash_backward_bytes(1, 4, 1024, 128),
        "sliding_attention_flops_per_step": flops_laguna.attention_flops(
            laguna, 1, 1024, flops_laguna.SLIDING),
        "sliding_kernel_bytes_per_step": flops_laguna.flash_kernel_bytes(
            laguna, 1, 1024, flops_laguna.SLIDING),
        "mla_kernel_flops_per_step": flops_xing.mla_kernel_flops(
            latent, 1, 1024),
        "mla_kernel_bytes_per_step": flops_xing.mla_kernel_bytes(
            latent, 1, 1024)}


def _required(facts, family):
    """(FLOPs, bytes) a step the family's backward requires, by hand."""
    if family == "flash_bwd":
        return (facts["attention_flops_per_step"] * 2 / 3,
                facts["attention_bwd_bytes_per_step"])
    if family == "flash_win_bwd":
        return (facts["sliding_attention_flops_per_step"] * 2 / 3,
                facts["sliding_kernel_bytes_per_step"]["bwd"])
    return (facts["mla_kernel_flops_per_step"]["bwd"],
            facts["mla_kernel_bytes_per_step"]["bwd"])


FAMILIES = {"flash_bwd": "kernel.flash_bwd_roofline",
            "flash_win_bwd": "kernel.flash_win_bwd_roofline",
            "flash_mla_bwd": "kernel.flash_mla_bwd_roofline"}


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_a_family_reads_the_same_work_fused_or_split(monkeypatch, family):
    """The fused call, or the ``_dq`` + ``_dkv`` pair in the same device
    time: the same required work, so the same reading; and a sibling
    family's calls are not this one's."""
    reader = harness.layer_readers((harness.HERE,))[FAMILIES[family]]
    facts = _facts()
    reading = harness.Reading(facts=facts, trace=object(), peaks=PEAKS,
                              compiles_in_window=0)
    work, moved = _required(facts, family)
    least_s = max(work / 1.97e14, moved / 8.19e11)
    assert work / 1.97e14 > moved / 8.19e11          # compute-bound here
    others = [(f + "_dq", 3.0) for f in FAMILIES if f != family]
    got = {}
    for shape, calls in (
            ("fused", [(family, 20.0)]),
            ("split", [(family + "_dq", 8.0), (family + "_dkv", 12.0)])):
        prog = traced(calls + others + [("flash_fwd", 5.0)])
        assert prog.by_kernel_s[family] == pytest.approx(2 * 20e-3), shape
        assert prog.by_call_s == pytest.approx(prog.by_kernel_s), shape
        monkeypatch.setattr(P, "of_reading", lambda r, prog=prog: prog)
        got[shape] = reader.read(reading)
    assert got["fused"] == pytest.approx(got["split"])
    assert got["fused"] == pytest.approx(100 * least_s / 20e-3)
    # a program with none of the family's calls, or a run without a trace
    monkeypatch.setattr(P, "of_reading",
                        lambda r: traced([("flash_fwd", 5.0)] + others))
    assert reader.read(reading) is None
    monkeypatch.undo()
    assert reader.read(harness.Reading(facts, None, PEAKS, 0)) is None
    # ... or a runner that gave no count
    monkeypatch.setattr(P, "of_reading",
                        lambda r: traced([(family, 20.0)]))
    assert reader.read(harness.Reading({"chips": 1}, object(), PEAKS,
                                       0)) is None


def test_a_fused_backward_at_the_mxus_peak_reads_its_ceiling(monkeypatch):
    """Five matmuls a tile issued for the four required: a fused kernel that
    ran at the bf16 peak would read 80%; the latent one 1,280 of 1,664."""
    readers = harness.layer_readers((harness.HERE,))
    facts = _facts()
    reading = harness.Reading(facts=facts, trace=object(), peaks=PEAKS,
                              compiles_in_window=0)
    for family, issued_over_required, ceiling in (
            ("flash_bwd", 5 / 4, 80.0), ("flash_win_bwd", 5 / 4, 80.0),
            ("flash_mla_bwd", 1664 / 1280, 100 * 1280 / 1664)):
        work, _ = _required(facts, family)
        ms = 1e3 * issued_over_required * work / 1.97e14
        monkeypatch.setattr(P, "of_reading", lambda r, f=family, ms=ms:
                            traced([(f, ms)]))
        assert readers[FAMILIES[family]].read(reading) == pytest.approx(
            ceiling), family


def test_the_required_work_by_hand():
    facts = _facts()
    # dense: forward 1, backward 2 of 2 x 2 x B H T^2 D / 2 a layer
    fwd = 2 * (2 * 1 * 4 * 1024 * 1024 * 128) / 2
    assert facts["attention_flops_per_step"] == 2 * 3 * fwd
    # q k v o do dq dk dv of 1 x 4 x 1024 x 128 bf16 + f32 row statistics
    assert flops.flash_backward_bytes(1, 4, 1024, 128) == (
        8 * 4 * 1024 * 128 * 2 + 4 * 1024 * 4)
    assert flops.flash_backward_bytes(1, 1, 4, 2) == 144     # test_flops.py
    # the band: q o do dq at 8 heads, k v dk dv at 2, one statistic a row
    tensor = 1024 * 128 * 2
    band = facts["sliding_kernel_bytes_per_step"]
    pairs = 512 * 513 // 2 + 512 * 512             # window 512 of 1024
    assert facts["sliding_attention_flops_per_step"] == (
        3 * 4.0 * 2 * 8 * pairs * 128)
    assert band["bwd"] == 2 * ((4 * 8 + 4 * 2) * tensor + 8 * 1024 * 4)
    assert band["bwd"] < band["dq"] + band["dkv"]     # the pair reads twice
    # latent: dP 256 + dQ 384 + dV 256 + dK 384 a pair and head
    pairs = 2 * 4 * (1024 * 1025 // 2)
    assert facts["mla_kernel_flops_per_step"]["bwd"] == 1280 * pairs
    assert facts["mla_kernel_flops_per_step"]["fwd"] == 640 * pairs
    rows = 1024 * 4
    assert facts["mla_kernel_bytes_per_step"]["bwd"] == 2 * (
        2 * (rows * (192 + 128 + 128) * 2 + 1024 * 64 * 2)
        + 2 * rows * 128 * 2 + rows * 4)


# ------------------------------------------------------- the walks' names
@pytest.mark.parametrize("name, category", [
    ("flash_fwd.6", "mosaic"), ("flash_bwd.10", "mosaic"),
    ("flash_win_bwd.8", "mosaic"), ("flash_mla_bwd_dkv.15", "mosaic"),
    ("kda_fwd.26", "mosaic"), ("kda_bwd.15", "mosaic"),
    ("closed_call.6", "mosaic"),             # an unnamed pallas_call
    ("ragged-dot-none.8", "matmul"), ("ragged-dot-metadata.2", "matmul"),
    ("row_update.3", "scatter_gather")])
def test_classify_books_a_named_custom_call_where_its_work_belongs(name,
                                                                   category):
    assert R.classify(CALL.format(name)) == category
    # only a tpu_custom_call is told by its name
    assert R.classify(f"%{name} = f32[8]{{0}} custom-call(%a), "
                      'custom_call_target="Sharding"') == "other"


def test_the_grouped_matmul_is_neither_unscoped_nor_flash():
    ops = [R.Event(CALL.format("ragged-dot-none.8"), 0, 30 * MS),
           R.Event(CALL.format("flash_bwd.10"), 30 * MS, 50 * MS),
           R.Event(CALL.format("row_update.3"), 50 * MS, 60 * MS),
           R.Event("%copy.4 = f32[8]{0} copy(%a)", 60 * MS, 64 * MS)]
    index = Index({"ragged-dot-none.8": "ragged-dot-none",
                   "flash_bwd.10": BWD + "flash_bwd/flash_bwd/pallas_call",
                   "row_update.3": "jit(step)/tables.scatter_apply/"
                                   "row_update/pallas_call"})
    trace = R.Trace(
        devices={"/device:TPU:0": R.DeviceLines(
            ops=ops, modules=[R.Event("jit_step(1)", 0, 64 * MS)])},
        host=[R.Event(R.WINDOW_SPAN, 0.0, 100 * MS)])
    prog = P.summarize(trace, index)
    assert prog.by_kernel_s == pytest.approx({
        "ragged-dot": 30e-3, "flash_bwd": 20e-3, "row_update": 10e-3})
    assert prog.by_call_s == pytest.approx({"flash_bwd": 20e-3})
    assert prog.by_scope_s == pytest.approx({
        "flash_bwd": 20e-3, "tables.scatter_apply": 10e-3})
    assert prog.unscoped_s == [("copy.4", pytest.approx(4e-3))]
    summary = R.summarize(trace)
    assert summary.by_category_s["matmul"] == pytest.approx(30e-3)
    assert summary.by_category_s["mosaic"] == pytest.approx(20e-3)
    assert summary.by_category_s["scatter_gather"] == pytest.approx(10e-3)
    assert [k for k, _ in R.breakdown(summary)["device_ops"]][:3] == [
        "ragged-dot-none.8 [matmul]", "flash_bwd.10 [mosaic]",
        "row_update.3 [scatter_gather]"]


def test_what_xla_books_to_a_kernels_name_is_the_familys_not_the_calls(
        monkeypatch):
    """A layout copy of a kernel's result carries the kernel's ``op_name``:
    a backward's roofline counts it (all device time under the name), the
    forward readers of PR 23 keep to the Mosaic call."""
    fwd = "jit(step)/jvp(layers)/while/body/attn/flash_fwd/flash_fwd/"
    ops = [R.Event(CALL.format("flash_fwd.6"), 0, 10 * MS),
           R.Event("%copy.7 = bf16[8]{0} copy(%a)", 10 * MS, 12 * MS),
           R.Event(CALL.format("flash_bwd.10"), 12 * MS, 30 * MS),
           R.Event(FUSION.format(11), 30 * MS, 32 * MS)]
    index = Index({"flash_fwd.6": fwd + "pallas_call", "copy.7": fwd + "x",
                   "flash_bwd.10": BWD + "flash_bwd/flash_bwd/pallas_call",
                   "fusion.11": BWD + "flash_bwd/convert_element_type"})
    trace = R.Trace(
        devices={"/device:TPU:0": R.DeviceLines(
            ops=ops, modules=[R.Event("jit_step(1)", 0, 32 * MS)])},
        host=[R.Event(R.WINDOW_SPAN, 0.0, 40 * MS)])
    prog = P.summarize(trace, index)
    assert prog.by_kernel_s == pytest.approx({"flash_fwd": 12e-3,
                                              "flash_bwd": 20e-3})
    assert prog.by_call_s == pytest.approx({"flash_fwd": 10e-3,
                                            "flash_bwd": 18e-3})
    monkeypatch.setattr(P, "of_reading", lambda r: prog)
    reading = harness.Reading(facts=_facts(), trace=object(), peaks=PEAKS,
                              compiles_in_window=0)
    readers = harness.layer_readers((harness.HERE,))
    third = _facts()["attention_flops_per_step"] / 3 / 1.97e14
    assert readers["kernel.flash_fwd_roofline"].read(
        reading) == pytest.approx(100 * third / 10e-3)
    assert readers["kernel.flash_bwd_roofline"].read(
        reading) == pytest.approx(100 * 2 * third / 20e-3)


# ------------------------------------------------------ what the PR retired
def test_the_retired_metrics_are_nowhere():
    gone = ("kernel.flash_dq_roofline", "kernel.flash_dkv_roofline",
            "kernel.flash_win_dq_roofline", "kernel.flash_win_dkv_roofline",
            "kernel.flash_mla_dq_roofline", "kernel.flash_mla_dkv_roofline",
            "apps.batcher_ms_per_step")
    with open(os.path.join(harness.REPO, "BENCHMARK.json")) as f:
        text = f.read()
    for root, _, files in os.walk(harness.HERE):
        for name in files:
            path = os.path.join(root, name)
            if path == os.path.abspath(__file__) or not name.endswith(
                    (".py", ".json", ".txt", ".toml", ".csv")):
                continue
            with open(path) as f:
                text += f.read()
    assert not [g for g in gone if f'"{g}"' in text or f"``{g}``" in text]
    declared = {m["name"]: m for m in json.loads(
        open(os.path.join(harness.REPO, "BENCHMARK.json")).read())[
            "per_layer"]}
    dense = [c for c in declared["kernel.flash_fwd_roofline"]["workloads"]]
    assert declared["kernel.flash_bwd_roofline"]["workloads"] == dense
    assert declared["kernel.flash_win_bwd_roofline"]["workloads"] == \
        declared["kernel.flash_win_fwd_roofline"]["workloads"]
    assert declared["kernel.flash_mla_bwd_roofline"]["workloads"] == \
        declared["kernel.flash_mla_fwd_roofline"]["workloads"]


# ------------------------------------------------------- Laguna's settling
def test_lagunas_settling_is_a_fixed_number_in_its_configuration():
    cell = harness.load_cell("laguna-s-2.1-l5-e16.zipf-seq8k-b1")
    steps = cell.config["trainer"]["settle_steps"]
    assert isinstance(steps, int) and 0 < steps <= 90    # under 30 s at 3/s
    assert "settle_steps" in cell.config["assumed"]["settling"]
    assert "settle" not in json.dumps(cell.traffic)     # Xing's cell's too
    with open(os.path.join(harness.HERE, "runners",
                           "lm_train_kinds.py")) as f:
        runner = f.read()
    # the count comes from the file alone: no seed, no clock, no reading
    assert 'config["trainer"].get("settle_steps", 0)' in runner
    assert "range(self.settle_steps)" in runner
