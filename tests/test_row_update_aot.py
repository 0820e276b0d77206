"""The word2vec fused step at its published size, compiled ahead of time for
a described ``v5e:2x2`` (nothing executes, so nothing here is a measurement):
on a TPU a linear updater's rows go through the row-update kernel
(``ops/row_update.py``), which must keep both donated tables in place.

The kernel's numbers are in ``tests/test_updaters.py`` (interpret mode);
what only the TPU compiler can say is here: Mosaic takes the kernel at
3,000,000 x 384, the aliased tables are not copied, and the step fits.
"""

import os
import re

import numpy as np
import pytest

ROWS, DIM, STORED, BATCH, NEG = 3_000_000, 300, 384, 8192, 5
# The step's peak before the kernel was 8.7477 GiB (ledger, PR 30); the
# benchmark's bound on ``peak_hbm_gib`` is 1% (ISSUE 31).
PEAK_GIB = 8.835


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                    # no libtpu, or another chip
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def compiled_step(one_chip):
    """One compile for the module's tests (about 20 s)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import Mesh

    import multiverso_tpu as mv
    from multiverso_tpu import metrics
    from multiverso_tpu.apps import SkipGram

    patch = pytest.MonkeyPatch()
    # ``scatter_apply`` asks the process's backend; the lowering target is
    # what matters here.
    patch.setattr(jax, "default_backend", lambda: "tpu")
    # A program compiled for a described chip cannot be read back from the
    # persistent cache; keep the cache out of it.
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    mv.config.reset()
    if mv.initialized():
        mv.shutdown()
    try:
        mv.init(args=["-updater_type=sgd", "-sync=false",
                      "-log_level=error"],
                mesh=Mesh(np.asarray(jax.devices()[:1]), ("worker",)))
        app = SkipGram(64, DIM, learning_rate=1.0)
        step, _ = app.make_fused_step()

        def spec(dtype, *shape):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

        table, ids = spec(jnp.float32, ROWS, STORED), spec(jnp.int32, BATCH)
        kernel = metrics.counter("tables.scatter_traced", {"path": "kernel"})
        before = kernel.value
        compiled = step.lower(table, (), table, (), ids, ids,
                              spec(jnp.int32, BATCH, NEG)).compile()
        assert kernel.value == before + 2         # both tables' rows
        yield compiled
    finally:
        patch.undo()
        mv.shutdown()
        mv.config.reset()
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()


def test_fused_step_updates_both_tables_in_place(compiled_step):
    text = compiled_step.as_text()
    assert len(re.findall(r'custom_call_target="tpu_custom_call"',
                          text)) == 2
    assert not re.findall(rf"f32\[{ROWS},{STORED}\]\S* copy\(", text)
    assert not re.findall(
        rf"f32\[{ROWS // 8},8,{STORED}\]\S* copy\(", text)
    (din, _, dout, _, _, _, _), _ = compiled_step.input_formats
    out_in, _, out_out, _, _ = compiled_step.output_formats
    assert (out_in, out_out) == (din, dout)             # donation aliases


def test_fused_step_fits_its_bound(compiled_step):
    peak = compiled_step.memory_analysis().peak_memory_in_bytes
    assert peak <= PEAK_GIB * 2 ** 30, peak / 2 ** 30
