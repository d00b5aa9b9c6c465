"""Kernel: the least time the chip's HBM needs for the bytes the
window's bags require (:func:`bench.reference.needed_bytes`: distinct
rows per flush, output rows, ids), over the summed device time of the
crossbar kernel's events in the trace, in percent.  The bytes are those
of the work, not of the tiles the crossbar fetches, so any other
implementation of the same work is measured against the same bound."""

UNIT = "%"
LAYER = "kernel (kernels/crossbar_reduce)"
MOVES = "bags_per_s"


def read(m):
    if m.trace is None or not m.trace.kernel_s or not m.needed_bytes:
        return None
    least = m.needed_bytes / m.peaks["hbm_bytes_per_s"]
    return 100.0 * least / m.trace.kernel_s
