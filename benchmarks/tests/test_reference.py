"""The plain references against the system, small, on the CPU, where the
system can compute in float32 too: they must agree to rounding."""

import numpy as np
import pytest


def test_dense_reference_matches_the_program_in_float32():
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from multiverso_tpu.models import TransformerConfig, TransformerTrainer
    from multiverso_tpu.models.transformer import lm_loss

    from benchmarks.reference import dense_lm

    model = dict(vocab_size=384, dim=64, n_layers=3, n_heads=2, hidden=96,
                 max_seq=64, rope_theta=1e6, norm_eps=1e-6, scan_layers=True)
    cfg = TransformerConfig(**model, compute_dtype=jnp.float32)
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("dp",))
    trainer = TransformerTrainer(cfg, mesh, seed=3)
    tokens = jnp.asarray(np.random.RandomState(0).randint(
        384, size=(2, 64)).astype(np.int32))
    sys_loss, sys_grads = jax.value_and_grad(lm_loss)(
        trainer.params, tokens, cfg, mesh)
    ref_loss, ref_grads = dense_lm.loss_and_grads(
        trainer.params, tokens, model, layer=1)
    assert float(ref_loss) == pytest.approx(float(sys_loss), rel=1e-6)
    assert float(dense_lm.loss(trainer.params, tokens, model)) == \
        pytest.approx(float(sys_loss), rel=1e-6)

    def rel(a, b):
        return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))

    assert rel(sys_grads["embed"], ref_grads["embed"]) < 1e-5
    assert rel(sys_grads["out_norm"], ref_grads["out_norm"]) < 1e-5
    for leaf, grad in ref_grads["layer"].items():
        assert rel(sys_grads["layers"][leaf][1], grad) < 1e-5, leaf
    # A reference that agreed with anything would be worth nothing: a
    # wrong rotary base must show.
    wrong, _ = dense_lm.loss_and_grads(
        trainer.params, tokens, dict(model, rope_theta=1e4), layer=1)
    assert abs(float(wrong) - float(sys_loss)) > 1e-5


def test_sgns_reference_by_hand():
    """One pair, one negative, two dimensions, worked by hand."""
    from benchmarks.reference import sgns

    w_in = np.array([[1.0, 0.0]])
    w_out = np.array([[0.0, 1.0], [2.0, 0.0]])
    loss = sgns.step(w_in, w_out, np.array([0]), np.array([0]),
                     np.array([[1]]), lr=1.0)
    # pos = 0, neg = 2: loss = -log(1/2) - log(sigmoid(-2))
    sig2 = 1 / (1 + np.exp(-2.0))
    assert loss == pytest.approx(np.log(2) - np.log(1 - sig2))
    # d v = -(1/2) u_o + sig2 u_n; d u_o = -(1/2) v; d u_n = sig2 v
    assert w_in[0] == pytest.approx([1.0 - 2 * sig2, 0.5])
    assert w_out[0] == pytest.approx([0.5, 1.0])
    assert w_out[1] == pytest.approx([2.0 - sig2, 0.0])


def test_sgns_reference_sums_repeated_ids():
    from benchmarks.reference import sgns

    rng = np.random.RandomState(0)
    w_in, w_out = rng.randn(4, 3), rng.randn(5, 3)
    a_in, a_out = w_in.copy(), w_out.copy()
    c, o = np.array([1, 1, 2]), np.array([0, 0, 0])
    n = np.array([[3, 3], [4, 3], [3, 4]])
    sgns.step(a_in, a_out, c, o, n, lr=0.5)
    # the same batch one pair at a time, every gradient taken at the old
    # weights and scaled by the batch's 1/3: the sums must come out equal
    b_in, b_out = w_in.copy(), w_out.copy()
    for i in range(3):
        t_in, t_out = w_in.copy(), w_out.copy()
        sgns.step(t_in, t_out, c[i:i + 1], o[i:i + 1], n[i:i + 1],
                  lr=0.5 / 3)
        b_in += t_in - w_in
        b_out += t_out - w_out
    assert a_in == pytest.approx(b_in) and a_out == pytest.approx(b_out)
