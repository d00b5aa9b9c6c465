"""GPipe-style pipeline parallelism over a "stage" mesh axis.

``pipelined_apply`` runs ``M`` microbatches through ``S`` stages with the
classic fill/drain rotation: at tick ``t`` stage ``s`` processes
microbatch ``t - s`` (when valid) and hands its activation to stage
``s + 1`` via ``ppermute``.  Completion takes ``M + S - 1`` ticks; the
fill/drain overhead is :func:`bubble_fraction`.

The whole rotation is a single ``shard_map`` + ``lax.scan`` region so the
per-stage weights never leave their shard and XLA overlaps the ppermute
with the next tick's compute.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

def bubble_fraction(num_microbatches: int, num_stages: int) -> float:
    """Idle fraction of the ideal schedule: (S-1) / (M + S-1)."""
    if num_microbatches < 1 or num_stages < 1:
        raise ValueError("need at least one microbatch and one stage")
    return (num_stages - 1) / (num_microbatches + num_stages - 1)


def pipelined_apply(
    w: jax.Array,                  # (S, ...) stacked per-stage params
    x: jax.Array,                  # (M, microbatch, d) microbatched input
    body: Callable[[jax.Array, jax.Array], jax.Array],
    mesh,
) -> jax.Array:
    """Applies ``body(w[s], ·)`` for s = 0..S-1 over every microbatch.

    Returns the (M, microbatch, d) outputs of the final stage; numerically
    identical to running all stages sequentially on one device.
    """
    num_stages = _mesh_stage_size(mesh)
    if w.shape[0] != num_stages:
        raise ValueError(
            f"w has {w.shape[0]} stages but mesh 'stage' axis is {num_stages}"
        )
    num_micro = x.shape[0]
    ticks = num_micro + num_stages - 1
    perm = [(i, (i + 1) % num_stages) for i in range(num_stages)]

    def local(w_loc, x_all):
        stage = lax.axis_index("stage")
        w_stage = w_loc[0]

        def tick(carry, t):
            inbuf, outputs = carry
            m = t - stage
            # stage 0 draws fresh microbatches; later stages consume the
            # activation rotated in from the previous stage last tick
            fresh = x_all[jnp.clip(t, 0, num_micro - 1)]
            h_in = jnp.where(stage == 0, fresh, inbuf)
            h_out = body(w_stage, h_in)
            nxt = lax.ppermute(h_out, "stage", perm)
            m_clip = jnp.clip(m, 0, num_micro - 1)
            valid = (m >= 0) & (m < num_micro)
            outputs = outputs.at[m_clip].set(
                jnp.where(valid, h_out, outputs[m_clip])
            )
            return (nxt, outputs), None

        init = (jnp.zeros_like(x_all[0]), jnp.zeros_like(x_all))
        (_, outputs), _ = lax.scan(tick, init, jnp.arange(ticks))
        # only the final stage's records are the pipeline output; psum
        # broadcasts them so the out_spec can be replicated
        mine = jnp.where(stage == num_stages - 1, outputs, jnp.zeros_like(outputs))
        return lax.psum(mine, "stage")

    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P("stage"), P()),
        out_specs=P(),
    )(w, x)


def _mesh_stage_size(mesh) -> int:
    import numpy as np

    sizes = dict(zip(mesh.axis_names, np.shape(mesh.devices)))
    if "stage" not in sizes:
        raise ValueError(f"mesh {mesh.axis_names} has no 'stage' axis")
    return int(sizes["stage"])
