"""Host block compile: the window's host compile time of the flushes
(``stats.host_compile_s``) per flush dispatched in it."""

UNIT = "ms"
LAYER = "host block compile (core/reduction, serve/sharded._compile_and_dispatch)"
MOVES = "bags_per_s"


def read(m):
    if m.flushes <= 0:
        return None
    return m.host_compile_s / m.flushes * 1e3
