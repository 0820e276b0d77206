"""The seam between the model and its attention kinds
(``multiverso_tpu/models/attention/``): every kind's record is whole, its
leaves and their specs agree, its checks and refusals raise the messages they
always have, nothing under the seam imports the model, and a seeded draw of a
model with one layer of every kind stays what it was."""

import ast
import hashlib
import os
import re
import types

import jax
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from multiverso_tpu.models import TransformerConfig, init_params
from multiverso_tpu.models import transformer
from multiverso_tpu.models.attention import KINDS, AttnKind
from multiverso_tpu.models.common import Draw
from multiverso_tpu.models.transformer import (LayerKind, _init_layer,
                                               _layer_pspecs)
from multiverso_tpu.ops import flash_eva, kda
from multiverso_tpu.ops.kernel_path import kernel_path

MODELS = os.path.dirname(transformer.__file__)
NAMES = ("full_attention", "sliding_attention", "latent_attention",
         "linear_attention", "eva_attention", "full_attention_nope")
# the kinds' scopes: ``attn.`` + the name's first word, but for the full
# attention that rotates nothing (PR 48)
SCOPES = dict({n: "attn." + n.split("_")[0] for n in NAMES},
              full_attention_nope="attn.full_nope")


def _model(attn, **over) -> dict:
    """Two layers of kind ``attn`` at toy widths, one dense and one routed,
    with every kind's own sizes given."""
    model = dict(
        vocab_size=64, dim=32, n_layers=2, n_heads=2, head_dim=16, hidden=16,
        max_seq=128, layer_types=[attn] * 2,
        mlp_layer_types=["dense", "sparse"], num_experts=4, top_k=2,
        sliding_window=8, kv_lora_rank=12, qk_nope_dim=8, qk_rope_dim=4,
        v_head_dim=8, eva_window=32, eva_chunk=4)
    model.update(over)
    return model


def _mesh(axes, shape):
    n = int(np.prod(shape))
    if len(jax.devices()) < n:
        pytest.skip("needs the virtual devices of tests/conftest.py")
    return Mesh(np.asarray(jax.devices()[:n]).reshape(shape), axes)


def test_the_six_kinds_are_known_in_their_order():
    assert tuple(KINDS) == NAMES


@pytest.mark.parametrize("name", NAMES)
def test_every_field_of_a_kind_is_set(name):
    kind = KINDS[name]
    assert isinstance(kind, AttnKind)
    assert kind.scope == SCOPES[name]
    assert len(set(SCOPES.values())) == len(NAMES)
    assert kind.saved and all(isinstance(s, str) for s in kind.saved)
    assert isinstance(kind.gate_tp, bool)
    for field in ("check", "init", "pspecs", "refuse", "rope", "heads"):
        assert callable(getattr(kind, field)), field
    # no mesh and one device are refused by none
    cfg = TransformerConfig(**_model(name))
    assert kind.refuse(cfg, None) is None
    assert kind.refuse(cfg, _mesh(("dp",), (1,))) is None
    assert cfg.rope(name).theta == cfg.rope_theta


@pytest.mark.parametrize("q_lora_rank", [0, 12])
@pytest.mark.parametrize("attn_gate", ["", "per_head"])
@pytest.mark.parametrize("name", NAMES)
def test_a_kinds_leaves_are_the_leaves_it_has_specs_for(name, attn_gate,
                                                        q_lora_rank):
    cfg = TransformerConfig(**_model(name, attn_gate=attn_gate,
                                     q_lora_rank=q_lora_rank))
    w = Draw(jax.random.key(0))
    mesh = _mesh(("dp",), (1,))
    for kind in cfg.layout.kinds:                 # a dense and a routed layer
        own = jax.eval_shape(lambda: KINDS[name].init(cfg, kind, w))
        assert set(own) == set(KINDS[name].pspecs(cfg, kind, None, 1))
        assert "wo" in own
        leaves = jax.eval_shape(lambda: _init_layer(cfg, kind, w))
        specs = _layer_pspecs(cfg, mesh, kind)
        assert set(leaves) == set(specs)
        assert ("wg" in leaves) == bool(attn_gate)
        for key, spec in specs.items():
            if isinstance(spec, P):
                assert len(spec) == leaves[key].ndim, key


@pytest.mark.parametrize("name,over,match", [
    ("full_attention", dict(n_heads=4, n_kv_heads=3),
     "4 query heads do not divide into 3 K/V heads"),
    ("sliding_attention", dict(n_heads=4, n_kv_heads=3),
     "4 query heads do not divide into 3 K/V heads"),
    ("sliding_attention", dict(sliding_window=0),
     "sliding_attention layers need sliding_window >= 1"),
    ("latent_attention", dict(kv_lora_rank=0), "latent_attention layers need"),
    ("latent_attention", dict(q_lora_rank=-1), "q_lora_rank >= 0"),
    ("latent_attention", dict(qk_norm=True),
     "latent_attention layers take no n_kv_heads or qk_norm"),
    ("linear_attention", dict(n_kv_heads=1),
     "linear_attention layers take no n_kv_heads or qk_norm"),
    ("linear_attention", dict(kda_lower_bound=0.5), "kda_lower_bound < 0"),
    ("linear_attention", dict(linear_conv_kernel=0),
     "linear_conv_kernel >= 1"),
    ("eva_attention", dict(qk_norm=True),
     "eva_attention layers take no n_kv_heads or qk_norm"),
    ("eva_attention", dict(eva_chunk=0),
     "eva_window a multiple of eva_chunk >= 1, got 32 / 0"),
    ("eva_attention", dict(eva_window=30),
     "eva_window a multiple of eva_chunk >= 1, got 30 / 4"),
])
def test_a_kind_checks_its_configuration_by_name(name, over, match):
    model = _model(name, **over)
    with pytest.raises(ValueError, match=match):
        TransformerConfig(**model)
    # the record alone says the same of anything with those attributes
    cfg = types.SimpleNamespace(**{**vars(TransformerConfig(**_model(name))),
                                   **over})
    with pytest.raises(ValueError, match=match):
        KINDS[name].check(cfg, LayerKind(name, model["n_heads"], "dense"))


def test_a_kind_nobody_wrote_is_an_unknown_layer_kind():
    with pytest.raises(ValueError, match="unknown layer kind"):
        TransformerConfig(**_model("block_attention"))
    with pytest.raises(ValueError, match="unknown layer kind"):
        TransformerConfig(**_model("full_attention",
                                   mlp_layer_types=["dense", "routed"]))


@pytest.mark.parametrize("name,axes,shape,match", [
    ("sliding_attention", ("dp", "sp"), (1, 2),
     r"sliding_attention layers \(window 8\) do not run over an 'sp' ring "
     r"\(sp=2\)"),
    ("latent_attention", ("dp", "sp"), (1, 2),
     "latent_attention does not run over sp=2: its two-part scores"),
    ("latent_attention", ("dp", "tp"), (1, 2),
     "latent_attention does not run over tp=2: no tp layout"),
    ("latent_attention", ("dp",), (2,),
     "latent_attention runs on one device: on a mesh of 2"),
    ("linear_attention", ("dp", "sp"), (1, 2),
     "linear_attention does not run over sp=2: the scan's state"),
    ("linear_attention", ("dp", "tp"), (1, 2),
     "linear_attention does not run over tp=2: no tp layout"),
    ("linear_attention", ("dp", "pp"), (1, 2),
     "linear_attention does not run over pp=2: pipeline stages"),
    ("linear_attention", ("dp",), (2,),
     "linear_attention runs on one device: on a mesh of 2"),
    ("eva_attention", ("dp", "sp"), (1, 2),
     "eva_attention runs on one device: on a mesh of 2 .*'sp': 2"),
    ("eva_attention", ("dp",), (2,),
     "eva_attention runs on one device: on a mesh of 2 .*'dp': 2"),
])
def test_a_kind_refuses_a_mesh_by_name(name, axes, shape, match):
    cfg = TransformerConfig(**_model(name))
    with pytest.raises(ValueError, match=match):
        KINDS[name].refuse(cfg, _mesh(axes, shape))


@pytest.mark.parametrize("axes,shape", [(("dp", "sp"), (1, 2)),
                                        (("dp", "tp"), (2, 2)),
                                        (("dp",), (4,))])
def test_full_attention_refuses_no_mesh(axes, shape):
    cfg = TransformerConfig(**_model("full_attention"))
    assert KINDS["full_attention"].refuse(cfg, _mesh(axes, shape)) is None


@pytest.mark.parametrize("name,shards", [
    ("full_attention", True), ("sliding_attention", True),
    ("latent_attention", False), ("linear_attention", False),
    ("eva_attention", None)])
def test_a_kind_shards_over_tp_or_says_it_does_not(name, shards):
    cfg = TransformerConfig(**_model(name, attn_gate="per_head"))
    kind = cfg.layout.kinds[0]
    mesh = _mesh(("dp", "tp"), (1, 2))
    if shards is False:
        with pytest.raises(ValueError,
                           match=f"{name} does not shard over 'tp' "
                                 r"\(tp=2\)"):
            _layer_pspecs(cfg, mesh, kind)
        return
    specs = _layer_pspecs(cfg, mesh, kind)
    tp = "tp" if shards else None
    assert specs["wq"] == P(None, tp) and specs["wo"] == P(tp, None)
    assert specs["wg"] == P(None, tp)


@pytest.mark.parametrize("path", sorted(
    [os.path.join("attention", f)
     for f in os.listdir(os.path.join(MODELS, "attention"))
     if f.endswith(".py")] + ["common.py"]))
def test_nothing_under_the_seam_imports_the_model(path):
    with open(os.path.join(MODELS, path)) as f:
        tree = ast.parse(f.read())
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            names = [node.module or ""] + [a.name for a in node.names]
        elif isinstance(node, ast.Import):
            names = [a.name for a in node.names]
        else:
            continue
        for name in names:
            assert not re.search(r"\btransformer\b", name), (
                f"{path}:{node.lineno} imports {name}")


def test_layers_default_to_the_first_kind():
    cfg = TransformerConfig(**_model("full_attention", layer_types=None))
    assert {k.attn for k in cfg.layout.kinds} == {NAMES[0]}


def test_a_sixth_kind_is_one_entry_of_kinds(monkeypatch):
    """The model knows a kind through ``KINDS`` alone: an entry nobody wrote
    a line of ``transformer.py`` for is checked, drawn, given specs, refused a
    mesh and run, under its own scope."""
    from multiverso_tpu.models.transformer import (param_shardings,
                                                   transformer_forward)

    def refuse(cfg, mesh):
        if mesh is not None and mesh.size > 1:
            raise ValueError("block_attention runs on one device")

    monkeypatch.setitem(KINDS, "block_attention", KINDS[NAMES[0]]._replace(
        scope="attn.block", refuse=refuse))
    tokens = np.arange(32, dtype=np.int32).reshape(2, 16)
    logits = {}
    for name in (NAMES[0], "block_attention"):
        cfg = TransformerConfig(**_model(name, compute_dtype="float32"))
        params = init_params(cfg, seed=3)
        param_shardings(cfg, _mesh(("dp",), (1,)))
        text = jax.jit(lambda p, t, cfg=cfg: transformer_forward(
            p, t, cfg)).lower(params, tokens).as_text(debug_info=True)
        assert ("attn.block" in text) == (name == "block_attention")
        logits[name] = np.asarray(transformer_forward(params, tokens, cfg))
    np.testing.assert_array_equal(*logits.values())
    with pytest.raises(ValueError, match="block_attention runs on one"):
        transformer_forward(params, tokens, cfg, _mesh(("dp",), (2,)))


def test_a_seeded_draw_of_every_kind_is_pinned():
    """One layer of the first five kinds (the sixth draws full attention's
    leaves), dense and routed FFNs, a gate, a shared
    expert, two streams and the module: a PR that moves a draw fails here and
    not in a cell's rate.  (PR 46, which drew the weights on the device from
    a seeded key, took the digest again; before it was 1efde8ac...8097.)"""
    cfg = TransformerConfig(**_model(
        "full_attention", n_layers=5, layer_types=list(NAMES[:5]),
        heads_per_layer=[2, 4, 2, 2, 2], n_kv_heads=0,
        mlp_layer_types=["dense", "sparse", "sparse", "dense", "sparse"],
        attn_gate="per_head", q_lora_rank=6, dense_hidden=24,
        shared_expert_hidden=8, router_scoring="sigmoid", aux_loss_coef=0.0,
        experts_held=2, hc_mult=2, mtp_layers=1, scan_layers=True))
    digest = hashlib.sha256()
    leaves, _ = jax.tree_util.tree_flatten_with_path(init_params(cfg, seed=7))
    for path, leaf in leaves:
        digest.update(f"{jax.tree_util.keystr(path)}|{leaf.shape}|"
                      f"{leaf.dtype}|".encode())
        digest.update(np.ascontiguousarray(leaf).tobytes())
    assert len(leaves) == 138
    assert digest.hexdigest() == (
        "8d09b733fb5f9fc6fa81165eb601132d03a60f189598bc971cd7d0a7546a141d")


# ------------------------------------------------- one kernel-path decision
@pytest.mark.parametrize("backend,force,no,want", [
    ("tpu", "", "", "mosaic"), ("tpu", "1", "", "mosaic"),
    ("tpu", "", "1", "jnp"), ("cpu", "", "", "jnp"),
    ("cpu", "1", "", "interpret"), ("cpu", "1", "1", "jnp"),
])
def test_one_function_decides_the_kernel_path(monkeypatch, backend, force,
                                              no, want):
    from multiverso_tpu.parallel import ring_attention

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setenv("MVTPU_FORCE_FLASH", force)
    monkeypatch.setenv("MVTPU_NO_FLASH", no)
    assert kernel_path() == want
    assert kda.kernel_path is kernel_path is flash_eva.kernel_path
    assert not hasattr(kda, "_path") and not hasattr(flash_eva, "_path")
    got = ring_attention._flash_dispatch(256, 256, 128)
    assert (got is None) == (want == "jnp")
    if got is not None:
        assert got[2] == (want == "interpret")
