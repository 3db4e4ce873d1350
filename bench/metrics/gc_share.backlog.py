"""Solve programs' device time outside the LC kernels, in the backlog cells (moves solves_per_s)."""
import layer


def read(ctx):
    return layer.gc_share(ctx)
