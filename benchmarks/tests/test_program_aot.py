"""The program's names in the step the chip would run: both dense cells'
step compiled for v5e at full size, as ``test_aot.py`` does, and read through
``benchmarks/trace/program.py``.  Nothing runs, so nothing here is a
measurement.  Skipped where the topology cannot be described."""

import collections
import re

import numpy as np
import pytest

from benchmarks import harness
from benchmarks.tests.test_aot import (no_compile_cache,  # noqa: F401
                                       param_shapes, topology)
from benchmarks.trace import program as P
from benchmarks.trace import reduce as R

DENSE = ["ouro-2.6b-l16-ut1.seq2k-b4", "ouro-2.6b-l16-ut1.seq8k-b1"]
# Instructions that are no work of their own on the device's op line.
FREE = {"parameter", "get-tuple-element", "tuple", "constant", "bitcast"}


@pytest.fixture(scope="module")
def compiled_text(topology):                              # noqa: F811
    texts = {}

    def compile_cell(name: str) -> str:
        if name in texts:
            return texts[name]
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P_

        from multiverso_tpu.models import (TransformerConfig,
                                           TransformerTrainer)
        from multiverso_tpu.models.transformer import param_shardings
        from multiverso_tpu.updaters import AddOption, get_updater

        cell = harness.load_cell(name)
        model, traffic = cell.config["model"], cell.traffic
        cfg = TransformerConfig(**model)
        mesh = Mesh(np.asarray(topology.devices[:cell.chips]).reshape(
            traffic["mesh"]["shape"]), tuple(traffic["mesh"]["axes"]))
        trainer = TransformerTrainer.__new__(TransformerTrainer)
        trainer.cfg, trainer.mesh = cfg, mesh
        trainer.updater = get_updater(cell.config["trainer"]["updater_type"])
        trainer.option = AddOption(
            learning_rate=cell.config["trainer"]["learning_rate"])
        params = jax.tree_util.tree_map(
            lambda shape, sharding: jax.ShapeDtypeStruct(
                shape, jnp.float32, sharding=sharding),
            param_shapes(model), param_shardings(cfg, mesh),
            is_leaf=lambda x: isinstance(x, tuple))
        state = jax.tree_util.tree_map(lambda p: (), params)
        tokens = jax.ShapeDtypeStruct(
            (traffic["batch"], traffic["seq"]), jnp.int32,
            sharding=NamedSharding(mesh,
                                   P_(traffic["mesh"]["axes"][0], None)))
        texts[name] = jax.jit(
            trainer._raw_step(), donate_argnums=(0, 1)).lower(
                params, state, tokens).compile().as_text()
        return texts[name]

    return compile_cell


def executed(text: str):
    """(instruction name, whole line) of what a device's op line would
    show: every instruction outside the fused computations."""
    fused = set(re.findall(r"fusion\(.*?calls=%([\w.\-]+)", text))
    current = None
    for raw in text.splitlines():
        head = R._COMPUTATION.match(raw)
        if head:
            current = head.group(1)
        elif raw.startswith("}"):
            current = None
        elif current is not None and current not in fused:
            ins = R._INSTRUCTION.match(raw)
            if ins and R._opcode(ins.group(2)) not in FREE:
                yield ins.group(1), raw.strip()


@pytest.mark.parametrize("name", DENSE)
def test_both_kernels_are_named_in_the_v5e_program(
        compiled_text, monkeypatch, name):
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("MVTPU_FORCE_FLASH", raising=False)
    text = compiled_text(name)
    index = P.ScopeIndex([text])
    calls = [(n, line) for n, line in executed(text)
             if 'custom_call_target="tpu_custom_call"' in line]
    # what a dense step holds since PR 35: the forward and ONE backward
    want = ["flash_bwd", "flash_fwd"]
    assert all(R.classify(line) == "mosaic" for _, line in calls)
    assert sorted(P.kernel(index.op_name(n)) for n, _ in calls) == want
    # The instruction itself is named after the kernel (``flash_fwd.6``),
    # which is what the ledger's ``breakdown`` prints.
    assert sorted(n.split(".")[0] for n, _ in calls) == want
    by_phase = {P.kernel(index.op_name(n)): P.phase(index.op_name(n))
                for n, _ in calls}
    assert by_phase == {"flash_fwd": "fwd", "flash_bwd": "bwd"}


@pytest.mark.parametrize("name", DENSE)
def test_unscoped_instructions_are_few_and_listed_by_name(
        compiled_text, monkeypatch, name):
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("MVTPU_FORCE_FLASH", raising=False)
    text = compiled_text(name)
    index = P.ScopeIndex([text])
    every = list(executed(text))
    loose = sorted(n for n, _ in every if P.unscoped(index.op_name(n)))
    # Listed by name; the prefetches (``copy-start.12`` ...) by their count.
    stems = collections.Counter(n.split(".")[0] for n in loose)
    moves = ("copy-start", "copy-done", "slice-start", "slice-done")
    print(f"{name}: {len(loose)} of {len(every)} executed instructions are "
          f"unscoped by the text: { {m: stems[m] for m in moves} } and "
          f"{[n for n in loose if n.split('.')[0] not in moves]}")
    # By the text alone: the compiler's prefetches and layout copies carry
    # no metadata.  None of them is a matmul fusion or a kernel.
    heavy = [n for n, line in every if n in set(loose)
             and R.classify(line, R.HloIndex([text])) in ("matmul", "mosaic")]
    assert heavy == []
    for scope in ("embed", "layers", "attn", "mlp", "head", "loss", "update"):
        assert any(P.scope(index.op_name(n)) == scope for n, _ in every), \
            scope
    phases = {P.phase(index.op_name(n)) for n, _ in every}
    assert {"fwd", "bwd", "remat", "update"} <= phases
