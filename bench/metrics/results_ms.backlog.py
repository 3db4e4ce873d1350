"""Slice-out and rate accounting of a batch's results, the service's own span, in the backlog cells (moves solves_per_s)."""
import layer


def read(ctx):
    return layer.span_ms(ctx, "results")
