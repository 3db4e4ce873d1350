"""Idle share of the device in the backlog cells (moves solves_per_s)."""
import layer


def read(ctx):
    return layer.idle_share(ctx)
