"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

Proves the distribution config is coherent without hardware: for each
cell, the train/serve step is jit-lowered with ShapeDtypeStruct inputs
(no allocation), compiled for the 256-chip single-pod mesh and the
512-chip two-pod mesh, and the compiled artifact's memory / cost /
collective footprint is recorded for §Dry-run and §Roofline.

Usage::

    PYTHONPATH=src python -m repro.launch.dryrun --all
    PYTHONPATH=src python -m repro.launch.dryrun --arch grok-1-314b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --arch dlrm-recross

Results are cached as JSON under experiments/dryrun/ (one file per cell);
``--force`` recomputes.
"""

# The host platform must present 512 devices BEFORE jax initializes —
# these two lines must stay the very first executable statements.
import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, SHAPES, get_config, supported_shapes
from repro.configs.base import ModelConfig, ShapeConfig
from repro.dist.sharding import (
    LOGICAL_RULES_MULTI_POD,
    LOGICAL_RULES_SINGLE_POD,
    activation_sharding_ctx,
    param_specs_for,
    sanitize_spec,
    sanitize_specs_tree,
)
from repro.launch.analytic import cell_cost
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import analyse, model_flops_for
from repro.models.transformer import init_lm
from repro.serve.decode import decode_step
from repro.serve.kvcache import init_cache
from repro.train.loop import TrainState, make_train_step
from repro.train.optimizer import AdamW, Adafactor, make_schedule

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..", "experiments", "dryrun")

# decode cells for huge KV caches use a bounded cache window per shape
DECODE_WINDOW = {"long_500k": 4096}


def pick_microbatches(cfg: ModelConfig, shape: ShapeConfig, dp: int,
                      *, target_gib: float = 9.0) -> int:
    """Grad-accumulation factor so saved activations fit next to params.

    Estimate: remat keeps ~4 residual-stream-sized tensors per layer per
    microbatch (layer input carry + attention/MLP block I/O), bf16.
    """
    b_local = max(shape.global_batch // dp, 1)
    per_mb_gib = (
        cfg.num_layers * b_local * shape.seq_len * cfg.d_model * 2 * 4 / 2**30
    )
    mb = 1
    while per_mb_gib / mb > target_gib and mb < shape.global_batch // dp and mb < 64:
        mb *= 2
    return mb


def pick_optimizer(cfg: ModelConfig):
    """Adafactor for ≥30B params (optimizer bytes/chip), AdamW otherwise."""
    sched = make_schedule(cfg.schedule, 3e-4, 10_000)
    if cfg.param_count() >= 30e9:
        return Adafactor(schedule=sched)
    return AdamW(schedule=sched)


def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> dict:
    """ShapeDtypeStruct stand-ins for every model input of this cell."""
    b, s = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    if shape.kind == "train":
        if cfg.family == "audio":
            toks = jax.ShapeDtypeStruct((b, cfg.num_codebooks, s), i32)
            labels = jax.ShapeDtypeStruct((b, cfg.num_codebooks, s), i32)
        else:
            toks = jax.ShapeDtypeStruct((b, s), i32)
            labels = jax.ShapeDtypeStruct((b, s), i32)
        batch = {"tokens": toks, "labels": labels}
        if cfg.family == "vlm":
            batch["enc"] = jax.ShapeDtypeStruct(
                (b, cfg.num_image_tokens, cfg.d_model), cfg.jnp_dtype
            )
        return batch
    if shape.kind == "prefill":
        if cfg.family == "audio":
            toks = jax.ShapeDtypeStruct((b, cfg.num_codebooks, s), i32)
        else:
            toks = jax.ShapeDtypeStruct((b, s), i32)
        out = {"tokens": toks}
        if cfg.family == "vlm":
            out["enc"] = jax.ShapeDtypeStruct(
                (b, cfg.num_image_tokens, cfg.d_model), cfg.jnp_dtype
            )
        return out
    # decode: one new token against a seq_len cache
    if cfg.family == "audio":
        toks = jax.ShapeDtypeStruct((b, cfg.num_codebooks, 1), i32)
    else:
        toks = jax.ShapeDtypeStruct((b, 1), i32)
    out = {"tokens": toks}
    if cfg.family == "vlm":
        out["enc"] = jax.ShapeDtypeStruct(
            (b, cfg.num_image_tokens, cfg.d_model), cfg.jnp_dtype
        )
    return out


# ------------------------------------------------------ sharding of state --


def _dp_axis(rules):
    return rules["batch"]


def batch_specs(batch_avals, rules, mesh):
    dp = _dp_axis(rules)

    def spec(a):
        parts = [dp] + [None] * (len(a.shape) - 1)
        return sanitize_spec(P(*parts), a.shape, mesh)

    return jax.tree.map(spec, batch_avals)


def opt_state_specs(opt_state_avals, params_specs, mesh):
    """Moments inherit param specs; factored/absent dims fall back cleanly."""
    p_leaves = jax.tree.leaves(params_specs, is_leaf=lambda x: isinstance(x, P))

    def for_moment_tree(tree_avals):
        leaves, treedef = jax.tree.flatten(tree_avals)
        out = []
        for aval, pspec in zip(leaves, p_leaves):
            parts = list(pspec)[: len(aval.shape)]
            out.append(sanitize_spec(P(*parts), aval.shape, mesh))
        return treedef.unflatten(out)

    if hasattr(opt_state_avals, "mu"):
        return type(opt_state_avals)(
            step=P(),
            mu=for_moment_tree(opt_state_avals.mu),
            nu=for_moment_tree(opt_state_avals.nu),
        )
    # Adafactor
    return type(opt_state_avals)(
        step=P(),
        vr=for_moment_tree(opt_state_avals.vr),
        vc=for_moment_tree(opt_state_avals.vc),
    )


_CACHE_MODEL_DIM_PRIORITY = {
    # key name -> candidate dims (index into shape) to shard by model.
    # K/V: kv-heads first, then SEQUENCE — never head_dim: a d-contracted
    # cache forces GSPMD to all-gather the whole cache every layer
    # (measured 98 GB/step on minicpm decode_32k, §Perf), while seq-sharded
    # caches reduce to output-sized psums.
    "k": (3, 2), "v": (3, 2), "k_scale": (3, 2), "v_scale": (3, 2), "pos": (),
    "h": (2, 3), "conv": (3,),
    "m_C": (2, 3), "m_n": (2, 3), "m_m": (2,),
    "s_c": (2,), "s_n": (2,), "s_h": (2,), "s_m": (2,),
}
_CACHE_BATCH_DIM = {
    "k": 1, "v": 1, "pos": 1, "h": 1, "conv": 1,
    "m_C": 1, "m_n": 1, "m_m": 1, "s_c": 1, "s_n": 1, "s_h": 1, "s_m": 1,
}


def cache_specs(cache_avals, rules, mesh, *, priority_override: dict | None = None):
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    model_n = sizes.get("model", 1)
    dp = _dp_axis(rules)
    prio = dict(_CACHE_MODEL_DIM_PRIORITY)
    if priority_override:
        prio.update(priority_override)

    def visit(path, aval):
        key = None
        for p in reversed(path):
            if hasattr(p, "key"):
                key = p.key
                break
        shape = aval.shape
        if not shape or key in (None, "len"):
            return P()
        parts = [None] * len(shape)
        bdim = _CACHE_BATCH_DIM.get(key)
        if bdim is not None and bdim < len(shape):
            parts[bdim] = dp
        for cand in prio.get(key, ()):
            if cand < len(shape) and shape[cand] % model_n == 0 and parts[cand] is None:
                parts[cand] = "model"
                break
        return sanitize_spec(P(*parts), shape, mesh)

    return jax.tree_util.tree_map_with_path(visit, cache_avals)


# ------------------------------------------------------------- the cells --


def run_cell(
    arch: str,
    shape_name: str,
    *,
    multi_pod: bool,
    results_dir: str = RESULTS_DIR,
    force: bool = False,
    remat: bool = True,
    variant: dict | None = None,
) -> dict:
    """One dry-run cell.  ``variant`` (hillclimb A/B knobs):
      name: str            — suffix for the result file
      rules: dict          — logical-rule overrides (e.g. {"seq": "model"} = SP)
      kv_quant: bool       — int8 KV cache (decode cells)
      readonly_cache: bool — batched-cache-write decode path
      cfg_overrides: dict  — dataclasses.replace overrides on the ModelConfig
      microbatches: int    — force a grad-accumulation factor
    """
    variant = variant or {}
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    cell_id = f"{arch}__{shape_name}__{mesh_name}"
    if variant.get("name"):
        cell_id += f"__{variant['name']}"
    os.makedirs(results_dir, exist_ok=True)
    out_path = os.path.join(results_dir, cell_id + ".json")
    if os.path.exists(out_path) and not force:
        with open(out_path) as f:
            return json.load(f)

    t0 = time.time()
    cfg = get_config(arch)
    if variant.get("cfg_overrides"):
        cfg = dataclasses.replace(cfg, **variant["cfg_overrides"])
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = LOGICAL_RULES_MULTI_POD if multi_pod else LOGICAL_RULES_SINGLE_POD
    if variant.get("rules"):
        rules = dict(rules, **variant["rules"])
    nchips = mesh.devices.size

    rng = jax.random.PRNGKey(0)
    params_avals = jax.eval_shape(lambda r: init_lm(r, cfg), rng)
    p_specs = sanitize_specs_tree(
        param_specs_for(params_avals, rules, moe=cfg.moe is not None),
        params_avals, mesh,
    )
    p_shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, s), p_specs,
        is_leaf=lambda x: isinstance(x, P),
    )

    record = {
        "cell": cell_id, "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "chips": nchips, "params": cfg.param_count(),
        "active_params": cfg.active_param_count(),
        "kind": shape.kind,
    }

    with activation_sharding_ctx(mesh, rules):
        if shape.kind == "train":
            optimizer = pick_optimizer(cfg)
            opt_avals = jax.eval_shape(optimizer.init, params_avals)
            o_specs = opt_state_specs(opt_avals, p_specs, mesh)
            state_avals = TrainState(
                params=params_avals, opt_state=opt_avals,
                step=jax.ShapeDtypeStruct((), jnp.int32),
            )
            state_shardings = TrainState(
                params=p_shardings,
                opt_state=jax.tree.map(
                    lambda s: NamedSharding(mesh, s), o_specs,
                    is_leaf=lambda x: isinstance(x, P),
                ),
                step=NamedSharding(mesh, P()),
            )
            batch_avals = input_specs(cfg, shape)
            b_specs = batch_specs(batch_avals, rules, mesh)
            b_shardings = jax.tree.map(
                lambda s: NamedSharding(mesh, s), b_specs,
                is_leaf=lambda x: isinstance(x, P),
            )
            dp_total = nchips // dict(zip(mesh.axis_names, mesh.devices.shape)).get("model", 1)
            microbatches = variant.get("microbatches") or pick_microbatches(cfg, shape, dp_total)
            record["microbatches"] = microbatches
            accum_dtype = jnp.bfloat16 if variant.get("accum_bf16") else jnp.float32
            step_fn = make_train_step(
                cfg, optimizer, remat=remat, microbatches=microbatches,
                has_enc=(cfg.family == "vlm"), accum_dtype=accum_dtype,
            )
            jitted = jax.jit(
                step_fn,
                in_shardings=(state_shardings, b_shardings),
                out_shardings=(state_shardings, None),
                donate_argnums=(0,),
            )
            lowered = jitted.lower(state_avals, batch_avals)
            record["optimizer"] = type(optimizer).__name__

        else:  # prefill / decode → serve path
            batch_avals = input_specs(cfg, shape)
            b_specs = batch_specs(batch_avals, rules, mesh)
            b_shardings = jax.tree.map(
                lambda s: NamedSharding(mesh, s), b_specs,
                is_leaf=lambda x: isinstance(x, P),
            )
            if shape.kind == "prefill":
                from repro.models.transformer import forward

                def serve_prefill(params, batch):
                    logits, _ = forward(
                        params, cfg, batch["tokens"], enc=batch.get("enc")
                    )
                    return logits

                jitted = jax.jit(
                    serve_prefill,
                    in_shardings=(p_shardings, b_shardings),
                )
                lowered = jitted.lower(params_avals, batch_avals)
            else:  # decode
                window = DECODE_WINDOW.get(shape_name, shape.seq_len)
                kv_quant = bool(variant.get("kv_quant"))
                cache_avals = jax.eval_shape(
                    lambda: init_cache(cfg, shape.global_batch, shape.seq_len,
                                       window=window, quant=kv_quant)
                )
                prio = None
                if variant.get("cache_seq_shard"):
                    # shard K/V caches on the sequence axis: attention over
                    # the cache contracts seq, so the collective payload is
                    # output-sized psums instead of gathered caches
                    prio = {
                        "k": (2,), "v": (2,),
                        "k_scale": (2,), "v_scale": (2,),
                    }
                c_specs = cache_specs(cache_avals, rules, mesh,
                                      priority_override=prio)
                c_shardings = jax.tree.map(
                    lambda s: NamedSharding(mesh, s), c_specs,
                    is_leaf=lambda x: isinstance(x, P),
                )

                # fleet default: read-only-cache decode (batched cache
                # writes; see §Perf decode iterations). The legacy
                # scan-carried-cache path remains selectable for A/B.
                readonly = bool(variant.get("readonly_cache", True)) or kv_quant

                def serve_decode(params, cache, batch):
                    return decode_step(
                        params, cfg, batch["tokens"], cache, enc=batch.get("enc"),
                        readonly_cache=readonly,
                    )

                jitted = jax.jit(
                    serve_decode,
                    in_shardings=(p_shardings, c_shardings, b_shardings),
                    out_shardings=(None, c_shardings),
                    donate_argnums=(1,),
                )
                lowered = jitted.lower(params_avals, cache_avals, batch_avals)

        compiled = lowered.compile()

    mem = compiled.memory_analysis()
    record["memory_analysis"] = {
        "argument_size_gib": mem.argument_size_in_bytes / 2**30,
        "output_size_gib": mem.output_size_in_bytes / 2**30,
        "temp_size_gib": mem.temp_size_in_bytes / 2**30,
        "alias_size_gib": mem.alias_size_in_bytes / 2**30,
        # donated outputs alias their arguments — subtract once
        "per_device_total_gib": (
            mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes
        ) / 2**30,
    }
    cost_kw = {}
    if shape.kind == "train":
        cost_kw = {"remat": remat, "optimizer": record.get("optimizer", "adamw").lower()}
    elif shape.kind == "decode":
        cost_kw = {"window": DECODE_WINDOW.get(shape_name)}
        if variant.get("kv_quant"):
            cost_kw["kv_dtype_bytes"] = 1.125
    acost = cell_cost(cfg, shape, **cost_kw)
    rep = analyse(
        arch=arch, shape=shape_name, mesh_name=mesh_name, chips=nchips,
        compiled=compiled, model_flops=model_flops_for(cfg, shape),
        analytic_flops=acost.flops, analytic_bytes=acost.hbm_bytes,
    )
    record["roofline"] = rep.to_dict()
    record["compile_seconds"] = time.time() - t0

    with open(out_path, "w") as f:
        json.dump(record, f, indent=1)
    return record


def run_dlrm_cell(*, multi_pod: bool, results_dir: str = RESULTS_DIR, force=False,
                  variant: dict | None = None) -> dict:
    """DLRM train-step dry-run (the paper's own model) on the big meshes.

    variant {"name": "hotrep", "hot_fraction": 0.02} enables the ReCross
    Eq.-1 replication applied as a SHARDING strategy: the hottest rows
    (remapped to low ids by the offline grouping phase) are stored
    REPLICATED across model shards — their gathers become collective-free;
    only the cold tail pays the sharded-gather exchange.
    """
    variant = variant or {}
    hot_fraction = float(variant.get("hot_fraction", 0.0))
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    cell_id = f"dlrm-recross__train_rec__{mesh_name}"
    if variant.get("name"):
        cell_id += f"__{variant['name']}"
    os.makedirs(results_dir, exist_ok=True)
    out_path = os.path.join(results_dir, cell_id + ".json")
    if os.path.exists(out_path) and not force:
        with open(out_path) as f:
            return json.load(f)
    t0 = time.time()
    from repro.configs.dlrm_recross import FULL as dcfg
    from repro.models.dlrm import init_dlrm

    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = LOGICAL_RULES_MULTI_POD if multi_pod else LOGICAL_RULES_SINGLE_POD
    dp = rules["batch"]
    rng = jax.random.PRNGKey(0)
    R, D = dcfg.rows_per_table, dcfg.embed_dim
    # pad tables to a 256 multiple so every sharding divides (standard)
    R = ((R + 255) // 256) * 256
    dcfg = dataclasses.replace(dcfg, rows_per_table=R)
    # hot rows occupy ids [0, H): the offline grouping phase remaps hot
    # groups to the head of the physical id space (frequency-descending),
    # so a Zipf-weighted query's lookups hit the replicated head w.p.
    # ~hot_coverage >> hot_fraction.
    H = int(R * hot_fraction)
    H = (H // 256) * 256

    params_avals = jax.eval_shape(lambda r: init_dlrm(r, dcfg), rng)
    if H:
        def split_tables(p):
            tabs = {}
            for k, v in p["tables"].items():
                tabs[k] = {"hot": v[:H], "cold": v[H:]}
            return dict(p, tables=tabs)

        params_avals = jax.eval_shape(split_tables, params_avals)

    def dlrm_spec(path, leaf):
        name = "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        if "/hot" in name or name.endswith("hot"):
            return P()  # replicated hot shard — Eq.1 at the sharding level
        if "tables" in name:
            return sanitize_spec(P("model", None), leaf.shape, mesh)
        if name.endswith("/w"):
            return sanitize_spec(P(None, "model"), leaf.shape, mesh)
        return P()

    p_specs = jax.tree_util.tree_map_with_path(dlrm_spec, params_avals)
    p_shard = jax.tree.map(lambda s: NamedSharding(mesh, s), p_specs,
                           is_leaf=lambda x: isinstance(x, P))
    B = 8192
    batch_avals = {
        "dense": jax.ShapeDtypeStruct((B, dcfg.dense_features), jnp.float32),
        "labels": jax.ShapeDtypeStruct((B,), jnp.float32),
        "sparse": {
            f"t{t}": jax.ShapeDtypeStruct((B, dcfg.max_bag), jnp.int32)
            for t in range(dcfg.num_tables)
        },
    }
    b_specs = jax.tree.map(
        lambda a: sanitize_spec(P(*([dp] + [None] * (len(a.shape) - 1))), a.shape, mesh),
        batch_avals,
    )
    b_shard = jax.tree.map(lambda s: NamedSharding(mesh, s), b_specs,
                           is_leaf=lambda x: isinstance(x, P))

    shardmap_bag = bool(variant.get("shardmap_bag"))
    model_n = dict(zip(mesh.axis_names, mesh.devices.shape)).get("model", 1)

    def _smbag(table, idx, rows):
        """shard_map sharded embedding bag: each model shard reduces its
        local rows, one psum of the (B_local, D) partials combines — the
        collective payload is OUTPUT-sized (B·D), not TABLE-sized."""

        def local(table_loc, idx_loc):
            shard = jax.lax.axis_index("model")
            r_loc = table_loc.shape[0]
            rel = idx_loc - shard * r_loc
            ok = (rel >= 0) & (rel < r_loc) & (idx_loc >= 0)
            take = table_loc[jnp.clip(rel, 0, r_loc - 1)] * ok[..., None].astype(table_loc.dtype)
            return jax.lax.psum(take.sum(axis=1), "model")

        return jax.shard_map(
            local, mesh=mesh,
            in_specs=(P("model", None), P(dp, None)),
            out_specs=P(dp, None),
        )(table, idx)

    def embed_bag(table_p, idx):
        """Padded gather+sum; hot/cold split when replicated head enabled;
        shard_map lookup when the smbag variant is on."""
        mask = (idx >= 0)[..., None].astype(jnp.float32)
        if H and shardmap_bag:
            # hot head: replicated, gathered locally with no collective;
            # cold tail: shard_map bag (psum of output-sized partials)
            hot, cold = table_p["hot"], table_p["cold"]
            is_hot = (idx < H) & (idx >= 0)
            e_hot = (hot[jnp.clip(idx, 0, H - 1)] * (is_hot[..., None] & (idx >= 0)[..., None])).sum(axis=1)
            cold_idx = jnp.where(is_hot | (idx < 0), -1, idx - H)
            return e_hot + _smbag(cold, cold_idx, R - H)
        if shardmap_bag:
            return _smbag(table_p, idx, R)
        if H:
            hot, cold = table_p["hot"], table_p["cold"]
            is_hot = (idx < H) & (idx >= 0)
            e_hot = hot[jnp.clip(idx, 0, H - 1)] * is_hot[..., None]
            e_cold = cold[jnp.clip(idx - H, 0, R - H - 1)] * (~is_hot)[..., None]
            take = (e_hot + e_cold) * mask
        else:
            take = table_p[jnp.clip(idx, 0, R - 1)] * mask
        return take.sum(axis=1)

    def loss_fn(params, batch):
        x = batch["dense"]
        for pl_ in params["bottom"]:
            x = jax.nn.relu(x @ pl_["w"] + pl_["b"])
        embs = [x] + [
            embed_bag(params["tables"][f"t{t}"], batch["sparse"][f"t{t}"])
            for t in range(dcfg.num_tables)
        ]
        stack = jnp.stack(embs, axis=1)
        inter = jnp.einsum("bnd,bmd->bnm", stack, stack)
        iu = jnp.triu_indices(stack.shape[1], k=1)
        top_in = jnp.concatenate([x, inter[:, iu[0], iu[1]]], axis=-1)
        for i, pl_ in enumerate(params["top"]):
            top_in = top_in @ pl_["w"] + pl_["b"]
            if i < len(params["top"]) - 1:
                top_in = jax.nn.relu(top_in)
        logits = top_in[:, 0]
        labels = batch["labels"]
        return jnp.mean(jnp.maximum(logits, 0) - logits * labels
                        + jnp.log1p(jnp.exp(-jnp.abs(logits))))

    def train_step(params, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        new_params = jax.tree.map(lambda p, g: p - 1e-3 * g.astype(p.dtype), params, grads)
        return new_params, loss

    with activation_sharding_ctx(mesh, rules):
        jitted = jax.jit(
            train_step, in_shardings=(p_shard, b_shard),
            out_shardings=(p_shard, None), donate_argnums=(0,),
        )
        lowered = jitted.lower(params_avals, batch_avals)
        compiled = lowered.compile()

    mem = compiled.memory_analysis()
    rep = analyse(arch="dlrm-recross", shape="train_rec", mesh_name=mesh_name,
                  chips=mesh.devices.size, compiled=compiled)
    record = {
        "cell": cell_id, "arch": "dlrm-recross", "shape": "train_rec",
        "mesh": mesh_name, "chips": mesh.devices.size,
        "memory_analysis": {
            "per_device_total_gib": (
                mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes) / 2**30,
        },
        "roofline": rep.to_dict(),
        "compile_seconds": time.time() - t0,
    }
    with open(out_path, "w") as f:
        json.dump(record, f, indent=1)
    return record


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--results-dir", default=RESULTS_DIR)
    args = ap.parse_args()

    archs = [args.arch] if args.arch else ARCH_IDS + ["dlrm-recross"]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    failures = []
    for arch in archs:
        if arch == "dlrm-recross":
            for mp in meshes:
                try:
                    rec = run_dlrm_cell(multi_pod=mp, results_dir=args.results_dir,
                                        force=args.force)
                    print(f"OK  {rec['cell']}  ({rec['compile_seconds']:.0f}s)")
                except Exception as e:
                    failures.append(("dlrm-recross", str(e)))
                    traceback.print_exc()
            continue
        cfg = get_config(arch)
        shapes = [args.shape] if args.shape else supported_shapes(cfg)
        for shape in shapes:
            for mp in meshes:
                try:
                    rec = run_cell(arch, shape, multi_pod=mp,
                                   results_dir=args.results_dir, force=args.force)
                    r = rec["roofline"]
                    print(
                        f"OK  {rec['cell']:60s} compile={rec['compile_seconds']:6.0f}s "
                        f"dom={r['dominant']:10s} frac={r['roofline_fraction']:.3f} "
                        f"mem/dev={rec['memory_analysis']['per_device_total_gib']:.1f}GiB"
                    )
                except Exception as e:
                    failures.append((f"{arch}/{shape}/mp={mp}", repr(e)))
                    traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for cell, err in failures:
            print(" ", cell, err[:200])
        raise SystemExit(1)
    print("\nall requested dry-run cells passed")


if __name__ == "__main__":
    main()
