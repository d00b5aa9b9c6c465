"""Mixture-of-Experts FFN with sort-free capacity dispatch.

Dispatch strategy (MaxText/T5X-style, memory-sane): for each token's
top-k choice, compute its *position within the expert's buffer* via a
cumulative-sum over the (tokens, experts) routing one-hot — an O(T·E)
intermediate, never the O(T·E·C) dispatch tensor.  Tokens are scattered
into a per-expert buffer ``(E, C, d)``, batch-matmul'd against stacked
expert weights (the einsum the ``model`` axis shards as expert
parallelism), and combined back with router weights.

Capacity ``C = ceil(T · top_k · cf / E)``; overflow tokens are dropped
(standard practice, cf=1.25 default) — drop fraction is returned for
monitoring and the aux load-balancing loss pushes the router away from
that regime.
"""

from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.dist.sharding import maybe_shard, maybe_shard_any
from repro.models.layers import Params, dense_init
from repro.configs.base import MoEConfig

# dispatch/combine buffers: shard capacity over data (token parallelism
# follows the batch), expert dim over model when it divides, else keep
# experts local and let the f-dim TP inside the einsum carry the model axis
_BUF_SHARDINGS = (
    ("experts", "expert_cap_dp", None),
    (None, "expert_cap_dp", None),
)
_HID_SHARDINGS = (
    ("experts", "expert_cap_dp", "mlp"),
    (None, "expert_cap_dp", "mlp"),
)


def init_moe(rng, d_model: int, d_ff: int, moe: MoEConfig, act: str, dtype) -> Params:
    kr, kg, kv, ko = jax.random.split(rng, 4)
    E = moe.num_experts
    p: Params = {
        "router": dense_init(kr, d_model, E, jnp.float32),  # router in f32
        "w_out": (jax.random.truncated_normal(ko, -3, 3, (E, d_ff, d_model)) * (0.5 / math.sqrt(d_ff))).astype(dtype),
        "w_val": (jax.random.truncated_normal(kv, -3, 3, (E, d_model, d_ff)) * (1.0 / math.sqrt(d_model))).astype(dtype),
    }
    if act in ("swiglu", "geglu"):
        p["w_gate"] = (jax.random.truncated_normal(kg, -3, 3, (E, d_model, d_ff)) * (1.0 / math.sqrt(d_model))).astype(dtype)
    return p


def apply_moe(
    p: Params,
    x: jax.Array,          # (b, s, d)
    moe: MoEConfig,
    act: str = "swiglu",
    *,
    num_groups: int = 1,
    shard_buffers: bool = True,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (y (b,s,d), aux_loss scalar).

    ``num_groups > 1`` enables GROUP-LOCAL dispatch (T5X/MaxText style):
    tokens are split into ``num_groups`` contiguous blocks, each with its
    own per-expert capacity ``C/num_groups`` and a block-local cumsum.
    When num_groups equals the data-parallel degree and the token axis is
    batch-sharded, every scatter stays shard-local — the cross-shard
    dispatch all-to-all disappears (§Perf granite/grok iterations).
    Dropping decisions become per-block instead of global (standard
    trade-off; same expected drop rate under a balanced router).
    """
    b, s, d = x.shape
    E, k = moe.num_experts, moe.top_k
    T = b * s
    xt = x.reshape(T, d)

    logits = (xt.astype(jnp.float32)) @ p["router"]         # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, k)                  # (T, k)
    top_w = top_w / jnp.clip(top_w.sum(-1, keepdims=True), 1e-9)

    # load-balancing aux loss (Switch): E * Σ_e f_e · P_e
    dispatch_onehot = jax.nn.one_hot(top_e, E, dtype=jnp.float32)  # (T, k, E)
    f = dispatch_onehot.sum(axis=(0, 1)) / (T * k)
    P = probs.mean(axis=0)
    aux = E * jnp.sum(f * P)

    G = num_groups if T % num_groups == 0 else 1
    Tg = T // G
    Cg = int(math.ceil(Tg * k * moe.capacity_factor / E))
    Cg = max(Cg, 8)
    C = G * Cg

    # position of each (token, choice) inside its expert's buffer —
    # cumsum runs WITHIN each token group; group g owns buffer rows
    # [g*Cg, (g+1)*Cg) so scatters never cross group (= shard) boundaries
    flat_e = top_e.reshape(G, Tg * k)                        # grouped choices
    choice_onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)  # (G, Tg*k, E)
    pos_in_e = jnp.cumsum(choice_onehot, axis=1) * choice_onehot
    position = pos_in_e.sum(axis=-1) - 1                     # (G, Tg*k)
    keep = position < Cg
    position = jnp.where(keep, position, Cg - 1) + jnp.arange(G)[:, None] * Cg
    flat_e = flat_e.reshape(T * k)
    position = position.reshape(T * k)
    keep = keep.reshape(T * k)

    # scatter tokens into per-expert buffers
    buf = jnp.zeros((E, C, d), x.dtype)
    tok_idx = jnp.repeat(jnp.arange(T), k)
    pos_clip = position
    buf = buf.at[flat_e, pos_clip].add(
        xt[tok_idx] * keep[:, None].astype(x.dtype)
    )
    if shard_buffers:
        buf = maybe_shard_any(buf, _BUF_SHARDINGS)

    # expert FFN: batched matmul over the expert axis (EP shards this)
    if "w_gate" in p:
        gate_act = jax.nn.silu if act == "swiglu" else jax.nn.gelu
        h = gate_act(jnp.einsum("ecd,edf->ecf", buf, p["w_gate"])) * jnp.einsum(
            "ecd,edf->ecf", buf, p["w_val"]
        )
    else:
        h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", buf, p["w_val"]))
    if shard_buffers:
        h = maybe_shard_any(h, _HID_SHARDINGS)
    out_buf = jnp.einsum("ecf,efd->ecd", h, p["w_out"])      # (E, C, d)
    if shard_buffers:
        out_buf = maybe_shard_any(out_buf, _BUF_SHARDINGS)

    # combine: gather each choice's result, weight, sum over k
    gathered = out_buf[flat_e, pos_clip] * keep[:, None].astype(x.dtype)  # (T*k, d)
    weighted = gathered * top_w.reshape(T * k, 1).astype(x.dtype)
    y = jnp.zeros((T, d), x.dtype).at[tok_idx].add(weighted)
    return y.reshape(b, s, d), aux


def apply_moe_shardmap(
    p: Params,
    x: jax.Array,          # (b, s, d) batch-sharded over the dp axes
    moe: MoEConfig,
    act: str = "swiglu",
) -> Tuple[jax.Array, jax.Array]:
    """shard_map MoE: dispatch is SHARD-LOCAL over the data axes.

    GSPMD's auto-partitioning of the capacity scatter materializes the
    dispatch as buffer-sized all-reduces (measured: granite train_4k moves
    ~1.6 TiB/step of all-reduce, §Perf).  Here the token→expert scatter and
    the expert→token combine never leave the data shard: the region is
    *manual* over the dp axes and *auto* over "model", so the expert
    einsums keep their tensor-parallel sharding, and the FSDP-sharded
    expert weights are explicitly all-gathered once per call (the cheap
    direction: weights ≪ dispatch buffers).

    Falls back to :func:`apply_moe` outside a mesh context.
    """
    from repro.dist.sharding import _current
    from jax.sharding import PartitionSpec as P

    rules, mesh = _current()
    if mesh is None:
        return apply_moe(p, x, moe, act)
    dp = rules.get("batch", "data")
    dp_axes = dp if isinstance(dp, tuple) else (dp,)
    manual = frozenset(dp_axes)

    def local(x_loc, router, w_gate, w_val, w_out):
        # gather the FSDP (data-dim) shards of the expert weights
        w_gate = _ag(w_gate, dp_axes, axis=1)
        w_val = _ag(w_val, dp_axes, axis=1)
        w_out = _ag(w_out, dp_axes, axis=2)
        pl = {"router": router, "w_gate": w_gate, "w_val": w_val, "w_out": w_out}
        y_loc, aux = apply_moe(pl, x_loc, moe, act, shard_buffers=False)
        return y_loc, jax.lax.pmean(aux, dp_axes[-1])

    in_specs = (
        P(dp, None, None),        # x: batch over dp
        P(),                      # router replicated
        P(None, dp, None),        # w_gate (E, d/fsdp, f)
        P(None, dp, None),        # w_val
        P(None, None, dp),        # w_out (E, f, d/fsdp)
    )
    out_specs = (P(dp, None, None), P())
    y, aux = jax.shard_map(
        local, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        axis_names=manual,
    )(x, p["router"], p["w_gate"], p["w_val"], p["w_out"])
    return y, aux


def _ag(w, dp_axes, *, axis):
    # route through f32: the transpose of a bf16 all_gather is a bf16
    # reduce-scatter, which crashes XLA-CPU's AllReducePromotion pass
    # (hlo_instruction.cc "Invalid binary instruction opcode copy").
    # On TPU this cast is unnecessary; cost here is 2x gather payload.
    orig = w.dtype
    w = w.astype(jnp.float32)
    for a in dp_axes:
        w = jax.lax.all_gather(w, a, axis=axis, tiled=True)
    return w.astype(orig)


def moe_flops_per_token(d_model: int, d_ff: int, moe: MoEConfig, act: str) -> int:
    """Active FLOPs per token (for 6ND-style accounting)."""
    mats = 3 if act in ("swiglu", "geglu") else 2
    return 2 * mats * d_model * d_ff * moe.top_k
