"""Dispatch: programs compiled, or loaded from the persistent cache,
inside the measured window (``jax.monitoring`` events); a warm-up that
covers every flush shape leaves none."""

UNIT = "count"
LAYER = "dispatch (kernels/sharded jit caches)"
MOVES = "bags_per_s"


def read(m):
    return m.window_compiles
