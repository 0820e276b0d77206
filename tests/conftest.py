"""Test harness: every distributed test runs on a virtual 8-device CPU mesh.

This is the proper version of the reference's single-process degenerate
mode (SURVEY.md §4): instead of one process holding both roles, we get a
real 8-way mesh on one host via XLA's forced host platform device count.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import pytest  # noqa: E402


def pytest_configure(config):
    # Tier-1 runs `-m 'not slow'` (ROADMAP.md): heavyweight sanitizer
    # sweeps opt out of the runtime budget with this marker, everything
    # else (mvlint, make analyze gate, TSan unit run) stays tier-1.
    config.addinivalue_line(
        "markers",
        "slow: heavyweight sweep (e.g. the ASan/UBSan multi-process "
        "scenario rebuild+run) excluded from tier-1 via -m 'not slow'")


@pytest.fixture()
def mv():
    """Fresh multiverso_tpu runtime per test."""
    import multiverso_tpu as mv

    mv.config.reset()
    if mv.initialized():
        mv.shutdown()
    yield mv
    if mv.initialized():
        mv.shutdown()
    mv.config.reset()


def dense_attention_ref(q, k, v, causal=True):
    """Shared dense attention reference for kernel/ring tests."""
    import jax
    import jax.numpy as jnp

    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bhtd,bhsd->bhts", q, k) * scale
    T = q.shape[2]
    if causal:
        mask = jnp.tril(jnp.ones((T, T), bool))
        s = jnp.where(mask[None, None], s, -jnp.inf)
    return jnp.einsum("bhts,bhsd->bhtd", jax.nn.softmax(s, -1), v)
