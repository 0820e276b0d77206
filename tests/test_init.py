"""The seeded draw of the initial weights (``init_params``,
``models/common.py:Draw``): on the device from ``jax.random.key(seed)``, every
leaf a function of (seed, layer index, leaf name) alone, a stacked slot made
stacked, every leaf on its sharding."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from multiverso_tpu import dashboard
from multiverso_tpu.models import (TransformerConfig, TransformerTrainer,
                                   init_params)
from multiverso_tpu.models.attention import KINDS
from multiverso_tpu.models.transformer import (group_layers, param_shardings,
                                               stack_layer_params)

# the five kinds of the pinned draw (PR 48's sixth, full attention that rotates
# nothing, draws full attention's leaves: tests/test_smallthinker.py)
NAMES = tuple(KINDS)[:5]
FULL, SLIDING = NAMES[:2]

# One layer of every kind, dense and routed FFNs, a gate, a shared expert, two
# streams and the module: the configuration of
# ``test_attention_kinds.py::test_a_seeded_draw_of_every_kind_is_pinned``.
EVERY_KIND = dict(
    vocab_size=64, dim=32, n_layers=5, n_heads=2, head_dim=16, hidden=16,
    max_seq=128, layer_types=list(NAMES), heads_per_layer=[2, 4, 2, 2, 2],
    mlp_layer_types=["dense", "sparse", "sparse", "dense", "sparse"],
    num_experts=4, top_k=2, sliding_window=8, kv_lora_rank=12, qk_nope_dim=8,
    qk_rope_dim=4, v_head_dim=8, eva_window=32, eva_chunk=4,
    attn_gate="per_head", q_lora_rank=6, dense_hidden=24,
    shared_expert_hidden=8, router_scoring="sigmoid", aux_loss_coef=0.0,
    experts_held=2, hc_mult=2, mtp_layers=1)
DENSE = dict(vocab_size=64, dim=32, n_layers=3, n_heads=2, hidden=48,
             max_seq=32)
# A lead, a period of (sliding, sliding, sliding, full) twice, a trail of two.
PERIODIC = dict(
    vocab_size=64, dim=32, n_layers=11, n_heads=2, hidden=16, max_seq=32,
    layer_types=[FULL] + [SLIDING, SLIDING, SLIDING, FULL] * 2 + [SLIDING] * 2,
    mlp_layer_types=["dense"] + ["sparse"] * 10, num_experts=4, top_k=2,
    layer_period=4, sliding_window=8)
# A period of five whose first four slots are alike: one run, stacked
# [n_periods, 4, ...].
RUN_OF_FOUR = dict(
    vocab_size=64, dim=32, n_layers=10, n_heads=2, hidden=16, max_seq=32,
    layer_types=[SLIDING, SLIDING, SLIDING, SLIDING, FULL] * 2,
    sliding_window=8)
MODELS = {"every_kind": EVERY_KIND, "dense": DENSE, "periodic": PERIODIC,
          "run_of_four": RUN_OF_FOUR}


def _leaves(tree):
    """``{path: numpy leaf}`` of a tree of the device's arrays."""
    return {jax.tree_util.keystr(path): np.asarray(leaf) for path, leaf
            in jax.tree_util.tree_flatten_with_path(jax.device_get(tree))[0]}


def _drawn(leaves):
    """The leaves a key made: not the gains, biases and streams' tables that
    rest at a constant."""
    return {path: leaf for path, leaf in leaves.items()
            if len(np.unique(leaf)) > 3}


# ------------------------------------------------ (a) a function of the seed
@pytest.mark.parametrize("scan", [False, True], ids=["loop", "scan"])
@pytest.mark.parametrize("name", sorted(MODELS))
def test_one_seed_is_one_tree_and_two_seeds_differ_in_every_drawn_leaf(name,
                                                                       scan):
    cfg = TransformerConfig(**MODELS[name], scan_layers=scan)
    first, again, other = (_leaves(init_params(cfg, seed=s))
                           for s in (5, 5, 6))
    assert first.keys() == again.keys() == other.keys()
    for path, leaf in first.items():
        assert leaf.dtype == np.float32, path
        assert leaf.tobytes() == again[path].tobytes(), path
    drawn = _drawn(first)
    assert len(drawn) >= 9
    for path, leaf in drawn.items():
        assert not np.array_equal(leaf, other[path]), path
    # no two leaves share a key: not by a name given twice, not across layers
    seen = {}
    for path, leaf in drawn.items():
        for row in leaf.reshape(-1, leaf.shape[-1])[:4]:
            assert seen.setdefault(row.tobytes(), path) == path


def test_a_seed_over_31_bits_is_a_seed():
    cfg = TransformerConfig(**DENSE)
    big, small = (_leaves(init_params(cfg, seed=s))
                  for s in (2 ** 31 + 11, 11))
    assert not np.array_equal(big["['embed']"], small["['embed']"])


# ------------------------------------------- (b) every leaf's stated scale
def _stated(path, leaf, cfg):
    """``(deviation, cut)`` a leaf of ``EVERY_KIND``'s loop-format tree is
    drawn with; ``cut`` in deviations, or None."""
    name = path.rsplit("['", 1)[1][:-2]
    if name in ("phi", "mu") and "hc_" not in path:
        return cfg.head_dim ** -0.5, 3.0
    if name == "phi":
        n = cfg.hc_mult
        return 0.02 * (n * cfg.dim) ** -0.5, None
    if name in ("router", "embed"):
        return 0.02, None
    if name.startswith("conv_"):
        return cfg.linear_conv_kernel ** -0.5, None
    return leaf.shape[-2] ** -0.5, None      # fan-in; an expert's own


_EVERY_CFG = TransformerConfig(**EVERY_KIND)
_EVERY_PATHS = sorted(
    jax.tree_util.keystr(path) for path, leaf
    in jax.tree_util.tree_flatten_with_path(
        jax.eval_shape(lambda: init_params(_EVERY_CFG)))[0]
    if leaf.ndim >= 2)


@pytest.fixture(scope="module")
def every_kind():
    return _leaves(init_params(_EVERY_CFG, seed=7))


@pytest.mark.parametrize("path", _EVERY_PATHS)
def test_a_drawn_matrix_has_its_stated_mean_and_deviation(every_kind, path):
    leaf = every_kind[path]
    std, cut = _stated(path, leaf, _EVERY_CFG)
    n = leaf.size
    if cut:
        assert np.max(np.abs(leaf)) <= cut * std * (1 + 1e-6)
        std *= 0.98658                       # of a normal cut at 3 deviations
    assert abs(leaf.mean()) < 5 * std / math.sqrt(n), (leaf.mean(), std)
    assert abs(leaf.std() / std - 1) < 5 / math.sqrt(2 * n), (leaf.std(), std)


def test_the_scans_time_scales_lie_where_the_library_draws_them(every_kind):
    a_log, dt_bias = (every_kind[f"['layers'][3]['{k}']"]
                      for k in ("A_log", "dt_bias"))
    assert np.all((np.exp(a_log) >= 1.0) & (np.exp(a_log) <= 16.0))
    dt = np.log1p(np.exp(dt_bias.astype(np.float64)))     # softplus
    assert np.all((dt > 0.001 * (1 - 1e-4)) & (dt < 0.1 * (1 + 1e-4)))
    assert len(np.unique(dt_bias)) == dt_bias.size


def test_init_std_is_every_matrix_but_the_routed_layers_own():
    cfg = TransformerConfig(**dict(EVERY_KIND, dim=64, init_std=0.01))
    for path, leaf in _drawn(_leaves(init_params(cfg, seed=1))).items():
        name = path.rsplit("['", 1)[1][:-2]
        if leaf.ndim < 2 or leaf.size < 2048 or name in ("phi", "mu"):
            continue
        routed = "shared" not in name and leaf.ndim == 3 or name == "router"
        assert (abs(leaf.std() / 0.01 - 1) < 0.1) != routed, path


# -------------------------------------- (c) a stacked slot is made stacked
@pytest.mark.parametrize("name", sorted(MODELS))
def test_scan_format_is_loop_format_stacked(name):
    cfg = TransformerConfig(**MODELS[name], scan_layers=True)
    loop = init_params(TransformerConfig(**MODELS[name]), seed=3)
    scan = init_params(cfg, seed=3)
    want = dict(loop, layers=group_layers(cfg, loop["layers"]))
    assert cfg.layout.uniform == (name == "dense")
    if cfg.layout.uniform:
        assert jax.tree_util.tree_structure(want["layers"]) == \
            jax.tree_util.tree_structure(stack_layer_params(loop["layers"]))
    want, got = _leaves(want), _leaves(scan)
    assert want.keys() == got.keys()
    for path, leaf in want.items():
        assert leaf.shape == got[path].shape, path
        assert leaf.tobytes() == got[path].tobytes(), path
    if name == "run_of_four":
        assert got["['layers']['period'][0]['wq']"].shape == (2, 4, 32, 32)


# ------------------------------ (d) a leaf's values are its name's and layer's
def test_a_leaf_added_to_a_kind_moves_no_other_leaf(monkeypatch):
    cfg = TransformerConfig(**EVERY_KIND, scan_layers=True)
    before = _leaves(init_params(cfg, seed=7))
    kind = KINDS[SLIDING]

    def init(cfg, layer_kind, w):
        extra = w("extra", cfg.dim, 3)            # drawn first
        return dict(kind.init(cfg, layer_kind, w), extra=extra)

    monkeypatch.setitem(KINDS, SLIDING, kind._replace(init=init))
    # (another configuration to the draw's cache: the kinds are not its key)
    after = _leaves(init_params(
        TransformerConfig(**dict(EVERY_KIND, max_seq=64), scan_layers=True),
        seed=7))
    added = set(after) - set(before)
    assert len(added) == 1 and added.pop().endswith("['extra']")
    for path, leaf in before.items():
        assert leaf.tobytes() == after[path].tobytes(), path


# ------------------------------------ (e) every leaf is born on its sharding
@pytest.mark.parametrize("name,axes,shape", [
    ("dense", ("dp", "tp"), (2, 2)), ("dense", ("dp",), (4,)),
    ("periodic", ("dp", "tp"), (2, 2))])
def test_every_leaf_is_drawn_on_its_sharding_and_no_host_draw_runs(
        monkeypatch, name, axes, shape):
    if len(jax.devices()) < 4:
        pytest.skip("needs the virtual devices of tests/conftest.py")

    def refuse(*a, **k):
        raise AssertionError("a numpy generator was asked for")

    # (``RandomState.randn`` itself cannot be patched: the type is immutable)
    monkeypatch.setattr(np.random, "RandomState", refuse)
    monkeypatch.setattr(np.random, "default_rng", refuse)
    cfg = TransformerConfig(**MODELS[name], scan_layers=True)
    mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(shape), axes)
    shardings = param_shardings(cfg, mesh)
    trainer = TransformerTrainer(cfg, mesh, seed=9)
    placed = jax.tree_util.tree_leaves(trainer.params)
    wanted = jax.tree_util.tree_leaves(shardings)
    assert len(placed) == len(wanted)
    for leaf, sharding in zip(placed, wanted):
        assert isinstance(leaf, jax.Array) and leaf.sharding == sharding
    if "tp" in axes:
        assert any(not s.is_fully_replicated for s in wanted)
    # ... and holds what one device draws alone
    alone = _leaves(init_params(cfg, seed=9))
    for path, leaf in _leaves(trainer.params).items():
        assert leaf.tobytes() == alone[path].tobytes(), path


# --------------------------------------------------- (f) the two monitors
def test_a_trainers_draw_and_placement_are_both_timed():
    dashboard.reset()
    cfg = TransformerConfig(**DENSE, scan_layers=True)
    trainer = TransformerTrainer(
        cfg, Mesh(np.asarray(jax.devices()[:1]), ("dp",)), "adagrad", seed=0)
    ran = dashboard.report(log=False)
    draw, place = (ran[f"Transformer::init_{k}"] for k in ("draw", "place"))
    assert draw.count == place.count == 1
    assert draw.total_s > 0 and place.total_s > 0
    assert all(isinstance(p, jax.Array)
               for p in jax.tree_util.tree_leaves(trainer.params))
    assert float(jnp.sum(jnp.abs(trainer.state["embed"][0]))) == 0.0
