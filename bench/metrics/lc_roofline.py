"""LC kernels' share of their HBM roofline (moves solves_per_s)."""
import layer


def read(ctx):
    return layer.lc_roofline(ctx)
