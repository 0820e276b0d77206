"""Tier-1 (CPU) cover for ``chip_smoke.py`` and the compile-cache helper.

The smoke itself only means something on the chip; what the CPU can
hold is everything around it: it refuses a non-TPU backend before
compiling, a failed phase cannot end in exit 0, its phase functions run
end to end at a tiny config on the 8-device CPU mesh (the flash kernel in
interpret mode through THIS test's flag — ``main()`` refuses that flag),
the flagship step cross-lowers for ``tpu`` holding the three Mosaic
kernels, and the compile cache lands where the contract says.
"""

import os
import re
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def test_refuses_cpu_before_compiling():
    """`python chip_smoke.py` on a CPU backend: non-zero exit naming the
    platform, within seconds, and no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("MVTPU_FORCE_FLASH", None)
    out = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                         capture_output=True, text=True, timeout=120,
                         env=env, cwd=REPO)
    assert out.returncode != 0, out.stdout
    assert "'cpu'" in out.stderr and "need 'tpu'" in out.stderr, out.stderr
    assert '"ok"' not in out.stdout, out.stdout


@pytest.mark.parametrize("var", ["MVTPU_NO_FLASH", "MVTPU_FORCE_FLASH"])
def test_refuses_attention_switches(monkeypatch, var):
    monkeypatch.setenv(var, "1")
    with pytest.raises(SystemExit) as exc:
        chip_smoke.check_device()
    assert var in str(exc.value)


def test_failed_phase_cannot_exit_zero(monkeypatch, capsys):
    """Inject a failure into a phase: main() must raise (a non-zero exit
    for the process) and print no result line."""
    monkeypatch.setattr(chip_smoke, "check_device", lambda: {
        "platform": "tpu", "kind": "injected", "count": 1})

    def boom():
        raise RuntimeError("injected phase failure")

    monkeypatch.setattr(chip_smoke, "phase_paper_surface", boom)
    with pytest.raises(RuntimeError, match="injected phase failure"):
        chip_smoke.main()
    assert '"ok"' not in capsys.readouterr().out


def test_last_line_is_the_verdict_alone(monkeypatch, capsys):
    """The driver reads the LAST stdout line: one JSON object with exactly
    ``ok`` and ``device`` {platform, kind, count}.  The report of the
    phases (ending ``"claim": null``) is the line before it, never it."""
    import json

    device = {"platform": "tpu", "kind": "injected", "count": 1}
    monkeypatch.setattr(chip_smoke, "check_device", lambda: dict(device))
    monkeypatch.setattr(chip_smoke, "phase_paper_surface", lambda: {})
    monkeypatch.setattr(chip_smoke, "phase_flash_reference",
                        lambda **shape: {})
    monkeypatch.setattr(chip_smoke, "phase_flash_latent", lambda: {})
    monkeypatch.setattr(chip_smoke, "phase_flagship",
                        lambda *a, **k: {"losses": [2.0, 1.0]})
    monkeypatch.setattr(chip_smoke, "phase_moe", lambda *a, **k: {})
    monkeypatch.setattr(chip_smoke, "phase_multichip", lambda *a, **k: {})
    assert chip_smoke.main() == 0
    lines = capsys.readouterr().out.splitlines()
    assert json.loads(lines[-1]) == {"ok": True, "device": device}
    report = json.loads(lines[-2])
    assert list(report)[-1] == "claim" and report["claim"] is None
    assert "ok" not in report
    assert report["flagship"] == {"losses": [2.0, 1.0]}


def test_check_losses_rejects_flat_and_nonfinite():
    chip_smoke.check_losses("ok", [2.0, 1.5])
    with pytest.raises(RuntimeError, match="did not fall"):
        chip_smoke.check_losses("flat", [2.0, 2.0])
    with pytest.raises(RuntimeError, match="non-finite"):
        chip_smoke.check_losses("nan", [2.0, float("nan")])


def test_phases_run_tiny_on_cpu_mesh(mv, monkeypatch):
    """Every phase function, tiny sizes, 8-device CPU mesh; the kernel
    runs in interpret mode, so the lowered step holds no Mosaic call."""
    import jax
    from jax.sharding import Mesh

    from multiverso_tpu.models import TransformerConfig

    monkeypatch.setenv("MVTPU_FORCE_FLASH", "interpret")
    mv.init(args=["-updater_type=sgd", "-sync=false", "-log_level=error"])
    assert chip_smoke.phase_tables(mv, size=1000, rows=64, cols=8)
    lr = chip_smoke.phase_lr(mv, batch=64, features=16, classes=4, steps=10)
    assert lr["loss_last"] < lr["loss_first"]
    w2v = chip_smoke.phase_w2v(mv, batch=64, vocab=512, dim=16, negatives=2,
                               steps=10)
    assert w2v["loss_last"] < w2v["loss_first"]
    # The published width: the tables store 384 columns, same checks.
    w2v = chip_smoke.phase_w2v(mv, batch=64, vocab=512, dim=300, negatives=2,
                               steps=10)
    assert w2v["loss_last"] < w2v["loss_first"] and w2v["dim"] == 300
    assert chip_smoke.phase_bsp(mv, size=16)
    # A batch that does not divide the replicas is an error, not a
    # replication.
    with pytest.raises(RuntimeError, match="does not divide"):
        chip_smoke.phase_lr(mv, batch=12, features=16, classes=4, steps=1)
    mv.shutdown()

    ref = chip_smoke.phase_flash_reference(batch=1, heads=2, seq=256,
                                           head_dim=64)
    assert max(ref["max_rel_err"].values()) < 3e-2, ref
    # grouped K/V heads under a window, as Laguna's sliding layers run
    ref = chip_smoke.phase_flash_reference(batch=1, heads=4, seq=256,
                                           head_dim=64, kv_heads=2,
                                           window=100)
    assert sorted(ref["max_rel_err"]) == ["dk", "dq", "dv", "o"]
    assert max(ref["max_rel_err"].values()) < 3e-2, ref
    latent = chip_smoke.phase_flash_latent(heads=2, seq=256, nope=32,
                                           rope=16, v_dim=32)
    assert sorted(latent["max_rel_err"]) == [
        "dk_nope", "dk_rope", "dq_nope", "dq_rope", "dv", "o"]
    assert max(latent["max_rel_err"].values()) < 3e-2, latent

    cfg = TransformerConfig(vocab_size=256, dim=64, n_layers=2, n_heads=2,
                            hidden=128, max_seq=256, scan_layers=True,
                            remat=True, remat_policy="dots")
    one = Mesh(np.asarray(jax.devices()[:1]), ("dp",))
    res = chip_smoke.phase_flagship(cfg, 4, 256, one, steps=4,
                                    kernels_per_step=0)
    assert res["kernels"] == {"tpu_custom_call": 0, "score_tensors": [],
                              "jnp_traces": 0}
    assert res["losses"][-1] < res["losses"][0]
    moe = chip_smoke.phase_moe(
        TransformerConfig(**dict(chip_smoke.MOE, vocab_size=256, dim=64,
                                 n_heads=2, hidden=32, max_seq=256)),
        2, 256, one, kernels_per_step=0)
    assert moe["losses"][-1] < moe["losses"][0]
    assert len(moe["expert_load_max_over_mean"]) == 2
    assert all(1.0 <= r < 8.0 for r in moe["expert_load_max_over_mean"])
    multi = chip_smoke.phase_multichip(cfg, 4, 256, res["losses"][0],
                                       kernels=(0, 0))
    assert set(multi) == {"dp4", "dp1_sp2_tp2"}
    with pytest.raises(RuntimeError, match="does not divide dp=4"):
        chip_smoke.phase_flagship(
            cfg, 6, 256, Mesh(np.asarray(jax.devices()[:4]), ("dp",)),
            kernels_per_step=0)


def test_flagship_step_cross_lowers_for_tpu(monkeypatch):
    """The flagship step at full width (depth cut to one layer) lowered
    for ``tpu`` from this CPU host: the forward and the one backward Mosaic
    kernel in the scanned layer's grad, and no [B,H,T,T] score tensor."""
    import jax
    from jax.sharding import Mesh

    from multiverso_tpu.models import TransformerConfig, TransformerTrainer

    # The dispatcher asks the process's backend; the lowering target is
    # what matters here.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("MVTPU_FORCE_FLASH", raising=False)
    cfg = TransformerConfig(**dict(chip_smoke.FLAGSHIP, n_layers=1))
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("dp",))
    tr = TransformerTrainer(cfg, mesh, updater_type="sgd")
    toks = np.zeros((chip_smoke.FLAGSHIP_BATCH, chip_smoke.FLAGSHIP_SEQ),
                    np.int32)
    text = tr.lowered_step(toks, lowering_platforms=("tpu",)).as_text()
    held = chip_smoke.held_kernels(text, cfg, chip_smoke.FLAGSHIP_BATCH,
                                   chip_smoke.FLAGSHIP_SEQ, mesh.shape)
    assert held == {"tpu_custom_call": 2, "score_tensors": []}, held


def test_held_kernels_sees_the_jnp_body():
    from multiverso_tpu.models import TransformerConfig

    cfg = TransformerConfig(**chip_smoke.FLAGSHIP)
    text = ("%0 = stablehlo.dot_general ... : tensor<4x16x2048x2048xf32>\n"
            "%1 = ... tensor<16x4x2048x2048xbf16>")      # residual, not a score
    held = chip_smoke.held_kernels(text, cfg, 4, 2048, {"dp": 1})
    assert held["score_tensors"] == ["tensor<4x16x2048x2048xf32>"]
    ring = chip_smoke.held_kernels("tensor<4x8x1024x512xf32>", cfg, 4, 2048,
                                   {"dp": 1, "sp": 2, "tp": 2})
    assert ring["score_tensors"] == ["tensor<4x8x1024x512xf32>"]


def test_compile_cache_placement(monkeypatch):
    """Variable set: the helper leaves jax's config alone (jax reads the
    variable itself).  Unset: ``<checkout>/.jax_cache``, a fixed path."""
    import jax

    from multiverso_tpu import compile_cache

    assert compile_cache.DEFAULT_DIR == os.path.join(REPO, ".jax_cache")
    before = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", "/sentinel/untouched")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
        assert compile_cache.configure() == "/somewhere/else"
        assert (jax.config.jax_compilation_cache_dir
                == "/sentinel/untouched")
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert compile_cache.configure() == compile_cache.DEFAULT_DIR
        assert (jax.config.jax_compilation_cache_dir
                == compile_cache.DEFAULT_DIR)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_one_cache_directory_assignment_in_the_tree():
    """No second ``jax_compilation_cache_dir`` assignment, and no cache
    path derived from tempfile, a pid or the clock."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import mvlint

    files = set()
    for path in mvlint.iter_py_files([REPO]):
        rel = os.path.relpath(path, REPO)
        if not rel.startswith("tests" + os.sep) and re.search(
                "jax_compilation_cache_dir|JAX_COMPILATION_CACHE_DIR",
                open(path, encoding="utf-8", errors="replace").read()):
            files.add(rel)
    assert files == {os.path.join("multiverso_tpu", "compile_cache.py"),
                     "chip_smoke.py"}, files
    src = open(os.path.join(REPO, "multiverso_tpu", "compile_cache.py")).read()
    for banned in ("tempfile", "getpid", "time."):
        assert banned not in src, banned
