#!/usr/bin/env python
"""step_hashes: what a tree's model layer makes for one benchmark cell, as
hashes, so that a PR that only moves code can show it traced the same program.

    python tools/step_hashes.py <tree> toy|full <cell> <out.json>
    python tools/step_hashes.py --compare <dir of the parent's> <dir of the change's>

``<tree>`` is a checkout (the parent's: ``git archive HEAD | tar -x -C
<dir>``).  ``toy``: the cell's configuration at toy widths with
``MVTPU_FORCE_FLASH=1`` (kernels in interpret mode), CPU devices, and two
steps' losses.  ``full``: the cell's configuration as published in
``benchmarks/configs``, lowered and compiled for an abstract v5e (no chip:
.claude/skills/verify/SKILL.md, "No chip needed"; ~3-11 min a cell).  Written:
sha256 of ``init_params`` leaf by leaf (names, order, bytes) at seed 2; sha256
of the lowered step's text with locations masked (a Mosaic body by the hash
of its own text without locations); the sorted multiset of the text's name
stacks, which masking drops and the benchmark's per-layer readers parse; the
compiled step's peak bytes.  ``--compare`` reads two directories of
``<mode>_<cell>.json`` and exits 1 unless every one of them is equal.  PR 43
ran it over the eight transformer cells (CHANGES.md); PR 48 added the
ninth's toy.

``init_sha256`` follows the draw, so across PR 46 (the weights drawn on the
device from a seeded key, where a numpy generator made them) it differs by
design, once, and with it the toy losses, which are made of the weights'
values; every other key (the lowered text, its names, the compiled peak) says
as before whether the step is the same program."""
import collections
import glob
import hashlib
import json
import os
import re
import sys
import time

KEYS = {
    "toy": ["init_sha256", "names_sha256", "toy_text_sha256",
            "toy_names_sha256", "toy_names_total", "toy_text_lines",
            "toy_loss_step0", "toy_loss_step1", "counters"],
    "full": ["init_sha256", "names_sha256", "full_text_sha256",
             "full_names_sha256", "full_names_total", "full_text_lines",
             "full_custom_calls", "full_payloads", "peak_memory_in_bytes",
             "compiled_remat_instructions"]}


def compare(parent_dir: str, change_dir: str) -> int:
    ok = True
    for pf in sorted(glob.glob(os.path.join(parent_dir, "*.json"))):
        name = os.path.basename(pf)
        cf = os.path.join(change_dir, name)
        if not os.path.exists(cf):
            print(name, "MISSING in", change_dir)
            ok = False
            continue
        with open(pf) as f, open(cf) as g:
            p, c = json.load(f), json.load(g)
        diff = [k for k in KEYS[p["mode"]] if p.get(k) != c.get(k)]
        print(name, f"DIFF {diff}" if diff else "EQUAL",
              p.get("peak_memory_in_bytes", ""))
        ok &= not diff
    print("ALL EQUAL" if ok else "NOT EQUAL")
    return 0 if ok else 1


if sys.argv[1] == "--compare":
    sys.exit(compare(*sys.argv[2:4]))

tree, mode, cell_name, out = sys.argv[1:5]
out = os.path.abspath(out)
tree = os.path.abspath(tree)
os.chdir(tree)
sys.path.insert(0, tree)
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ["ALLOW_MULTIPLE_LIBTPU_LOAD"] = "1"
os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
if mode == "toy":
    os.environ["MVTPU_FORCE_FLASH"] = "1"
else:
    os.environ.pop("MVTPU_FORCE_FLASH", None)
os.environ.pop("MVTPU_NO_FLASH", None)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

jax.config.update("jax_enable_compilation_cache", False)

from benchmarks import harness  # noqa: E402
from multiverso_tpu.models import TransformerConfig, TransformerTrainer  # noqa: E402
from multiverso_tpu.models.transformer import init_params, param_shardings  # noqa: E402
from multiverso_tpu.updaters import AddOption, get_updater  # noqa: E402
import multiverso_tpu.models.transformer as T  # noqa: E402

assert T.__file__.startswith(tree), T.__file__

SEED = 2
FULL, SLIDING = "full_attention", "sliding_attention"
LATENT, LINEAR, EVA = "latent_attention", "linear_attention", "eva_attention"


def toy_model(config_name: str, model: dict) -> dict:
    """The configuration's shape of layers at toy widths (the tests' own toy
    models, max_seq 256), keeping what the cell's file says of policy."""
    keep = {k: model[k] for k in ("scan_layers", "remat", "remat_policy")
            if k in model}
    if config_name.startswith("ouro"):
        toy = dict(vocab_size=512, dim=128, n_layers=4, n_heads=2, hidden=256,
                   max_seq=256, rope_theta=1e6, norm_eps=1e-6)
    elif config_name.startswith("olmoe"):
        toy = dict(vocab_size=512, dim=128, n_layers=3, n_heads=2, hidden=64,
                   max_seq=256, num_experts=8, top_k=2, norm_topk_prob=False,
                   moe_dispatch="grouped", aux_loss_coef=0.01,
                   router_z_loss_coef=0.001, qk_norm=True)
    elif config_name.startswith("laguna"):
        kinds = [FULL, SLIDING, SLIDING, SLIDING, FULL]
        toy = dict(
            vocab_size=96, dim=32, n_layers=5, n_heads=4, head_dim=8,
            n_kv_heads=2, hidden=16, dense_hidden=48, shared_expert_hidden=16,
            max_seq=256, norm_eps=1e-6, layer_types=kinds,
            heads_per_layer=[4 if k == FULL else 6 for k in kinds],
            mlp_layer_types=["dense"] + ["sparse"] * 4,
            layer_period=4, sliding_window=8,
            rope_full=dict(theta=5e5, rotary_factor=0.5, yarn_factor=8.0,
                           original_max_seq=16, beta_fast=32.0, beta_slow=1.0,
                           attention_factor=1.2),
            rope_sliding=dict(theta=1e4, rotary_factor=1.0),
            attn_gate="per_head", num_experts=8, experts_held=2,
            experts_first=2, top_k=3, norm_topk_prob=True, routed_scale=2.5,
            moe_dispatch="grouped", aux_loss_coef=0.0, router_z_loss_coef=0.0)
    elif config_name.startswith("xing"):
        n = 3
        toy = dict(
            vocab_size=96, dim=32, n_layers=n, n_heads=4, hidden=16,
            dense_hidden=48, shared_expert_hidden=16, max_seq=256,
            norm_eps=1e-6, layer_types=[LATENT] * n,
            mlp_layer_types=["dense"] + ["sparse"] * (n - 1),
            q_lora_rank=12, kv_lora_rank=10, qk_nope_dim=8, qk_rope_dim=4,
            v_head_dim=8, attn_mscale=1.2,
            rope_latent=dict(theta=1e4, yarn_factor=4.0, original_max_seq=16),
            num_experts=8, experts_held=2, experts_first=2, top_k=3,
            norm_topk_prob=True, routed_scale=2.0, router_scoring="sigmoid",
            router_bias_rate=0.001, moe_dispatch="grouped", aux_loss_coef=0.0,
            router_z_loss_coef=0.0, hc_mult=4, hc_sinkhorn_iters=3,
            mtp_layers=1, mtp_loss_coef=0.3)
    elif config_name.startswith("ling"):
        toy = dict(
            vocab_size=96, dim=32, n_layers=6, n_heads=2, head_dim=16,
            hidden=16, dense_hidden=48, shared_expert_hidden=16, max_seq=256,
            norm_eps=1e-6, layer_types=[LINEAR] * 5 + [LATENT],
            mlp_layer_types=["dense"] + ["sparse"] * 5, layer_period=6,
            attn_gate="per_head", q_lora_rank=0,
            kv_lora_rank=12, qk_nope_dim=8, qk_rope_dim=4, v_head_dim=8,
            rope_latent=dict(theta=6e6), linear_conv_kernel=4,
            kda_lower_bound=-5.0, num_experts=16, experts_held=4,
            experts_first=0, top_k=4, n_group=4, topk_group=2,
            norm_topk_prob=True, routed_scale=2.5, router_scoring="sigmoid",
            router_bias_rate=0.001, moe_dispatch="grouped", aux_loss_coef=0.0,
            router_z_loss_coef=0.0)
    elif config_name.startswith("evabyte"):
        toy = dict(
            vocab_size=320, dim=64, n_layers=4, n_heads=4, head_dim=16,
            hidden=96, max_seq=256, norm_eps=1e-5, rope_theta=1e5,
            layer_types=[EVA] * 4, eva_window=64, eva_chunk=4, n_pred_heads=8,
            norm_unit_offset=True, residual_dtype="float32",
            logits_dtype="float32", init_std=0.05)
    elif config_name.startswith("smallthinker"):
        kinds = ["full_attention_nope"] + [SLIDING] * 3
        toy = dict(
            vocab_size=96, dim=32, n_layers=4, n_heads=14, head_dim=8,
            n_kv_heads=2, hidden=16, max_seq=256, norm_eps=1e-6,
            layer_types=kinds, layer_period=4, sliding_window=16,
            rope_sliding=dict(theta=1.5e6, rotary_factor=1.0), num_experts=8,
            top_k=3, norm_topk_prob=True, moe_dispatch="grouped",
            aux_loss_coef=0.0, router_z_loss_coef=0.0, router_input="attn",
            ffn_act="relu")
    else:
        raise KeyError(config_name)
    toy.update(keep)
    return toy


def sha(b) -> str:
    return hashlib.sha256(b if isinstance(b, bytes) else b.encode()
                          ).hexdigest()


def tree_hash(params) -> dict:
    """sha256 over the leaves in order: path, shape, dtype, bytes."""
    h = hashlib.sha256()
    leaves, _ = jax.tree_util.tree_flatten_with_path(jax.device_get(params))
    names = []
    for path, leaf in leaves:
        a = np.asarray(leaf)
        name = jax.tree_util.keystr(path)
        names.append(name)
        h.update(f"{name}|{a.shape}|{a.dtype}|".encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return {"init_sha256": h.hexdigest(), "n_leaves": len(leaves),
            "names_sha256": sha("\n".join(names))}


# `loc(...)` references and the trailing `#loc... = loc(...)` table.
_LOC_LINE = re.compile(r"^#loc\d* = .*$", re.M)
_LOC_REF = re.compile(r" ?loc\((?:[^()]|\([^()]*\))*\)")
_NAMED = re.compile(r'^(#loc\d+) = loc\("((?:[^"\\]|\\.)*)"\(')
_USED = re.compile(r"loc\((#loc\d+)\)")
_BODY = re.compile(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22')


def _mask_payloads(text: str):
    """Mosaic bodies are serialised modules whose bytes hold their own debug
    locations: each is replaced by the sha256 of its text with locations
    stripped (parsed back with jaxlib's MLIR)."""
    import base64
    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir
    found = []

    def repl(m):
        ctx = mlir.make_ir_context()
        with ctx:
            ctx.allow_unregistered_dialects = True
            module = ir.Module.parse(base64.b64decode(m.group(1)))
            asm = module.operation.get_asm(enable_debug_info=False)
        found.append(sha(asm))
        return "body: <" + found[-1] + ">"

    return _BODY.sub(repl, text), found


def text_hashes(text: str, tag: str) -> dict:
    """The text with locations masked, and the multiset of the name stacks
    its operations carry (an op's `loc(#locN)` whose definition is
    `"<name stack>"(...)`; the call-stack frames, which are also named
    locations, are only ever referenced from other locations)."""
    named = {}
    for line in text.splitlines():
        if line.startswith("#loc"):
            m = _NAMED.match(line)
            if m:
                named[m.group(1)] = m.group(2)
    names = collections.Counter()
    for line in text.splitlines():
        if not line.startswith("#loc"):
            for ref in _USED.findall(line):
                if ref in named:
                    names[named[ref]] += 1
    masked = _LOC_REF.sub("", _LOC_LINE.sub("", text))
    masked, payloads = _mask_payloads(masked)
    masked = "\n".join(l for l in masked.splitlines() if l.strip())
    return {
        tag + "_text_sha256": sha(masked),
        tag + "_text_lines": masked.count("\n") + 1,
        tag + "_names_sha256": sha("\n".join(
            f"{n}\t{c}" for n, c in sorted(names.items()))),
        tag + "_names_distinct": len(names),
        tag + "_names_total": sum(names.values()),
        tag + "_custom_calls": text.count("@tpu_custom_call"),
        tag + "_payloads": payloads,
        "_names": sorted(names.items()),
    }


def main():
    cell = harness.load_cell(cell_name)
    config_name = cell_name.split(".")[0]
    model, traffic = cell.config["model"], cell.traffic
    result = {"cell": cell_name, "mode": mode, "tree": tree}
    t0 = time.time()
    if mode == "toy":
        cfg = TransformerConfig(**toy_model(config_name, model))
        shape = traffic["mesh"]["shape"]
        n_dev = int(np.prod(shape))
        mesh = Mesh(np.asarray(jax.devices()[:n_dev]).reshape(shape),
                    tuple(traffic["mesh"]["axes"]))
        batch = traffic["batch"]
        seq = 128
        params = init_params(cfg, SEED)
        result.update(tree_hash(params))
        trainer = TransformerTrainer(
            cfg, mesh, cell.config["trainer"]["updater_type"],
            AddOption(learning_rate=cell.config["trainer"]["learning_rate"]),
            seed=SEED)
        tokens = np.random.RandomState(SEED).randint(
            0, cfg.vocab_size, (batch, seq)).astype(np.int32)
        lowered = trainer.lowered_step(tokens)
        text = lowered.as_text(debug_info=True)
        result.update(text_hashes(text, "toy"))
        from multiverso_tpu import metrics
        result["counters"] = sorted(
            k for k in metrics.REGISTRY.render_prometheus().splitlines()
            if k.startswith("attention") or k.startswith("mv_attention"))
        # one step, so that a moved op that changes a number shows
        loss = float(trainer.train_step_async(tokens))
        result["toy_loss_step0"] = repr(loss)
        result["toy_loss_step1"] = repr(float(
            trainer.train_step_async(tokens)))
    else:
        from jax.experimental import topologies
        topology = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        jax.default_backend = lambda: "tpu"
        cfg = TransformerConfig(**model)
        mesh = Mesh(np.asarray(topology.devices[:cell.chips]).reshape(
            traffic["mesh"]["shape"]), tuple(traffic["mesh"]["axes"]))
        params = init_params(cfg, SEED)
        result.update(tree_hash(params))
        result["init_s"] = time.time() - t0
        shapes = jax.tree_util.tree_map(lambda a: a.shape, params)
        del params
        trainer = TransformerTrainer.__new__(TransformerTrainer)
        trainer.cfg, trainer.mesh = cfg, mesh
        trainer.updater = get_updater(cell.config["trainer"]["updater_type"])
        trainer.option = AddOption(
            learning_rate=cell.config["trainer"]["learning_rate"])
        sds = jax.tree_util.tree_map(
            lambda shape, sharding: jax.ShapeDtypeStruct(
                shape, jnp.float32, sharding=sharding),
            shapes, param_shardings(cfg, mesh),
            is_leaf=lambda x: isinstance(x, tuple))
        state = jax.tree_util.tree_map(lambda p: (), sds)
        tokens = jax.ShapeDtypeStruct(
            (traffic["batch"], traffic["seq"]), jnp.int32,
            sharding=NamedSharding(mesh, P(traffic["mesh"]["axes"][0], None)))
        lowered = jax.jit(trainer._raw_step(), donate_argnums=(0, 1)).lower(
            sds, state, tokens)
        text = lowered.as_text(debug_info=True)
        result.update(text_hashes(text, "full"))
        result["lower_s"] = time.time() - t0
        with open(out, "w") as f:           # the lowering's part, early
            json.dump(result, f, indent=1)
        compiled = lowered.compile()
        result["peak_memory_in_bytes"] = harness.compiled_peak_bytes(compiled)
        result["compiled_remat_instructions"] = compiled.as_text().count(
            ".remat")
    result["seconds"] = time.time() - t0
    with open(out, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps({k: v for k, v in result.items()
                      if not isinstance(v, list)}))
    with open(out + ".txt", "w") as f:
        f.write(text)


main()
