"""Admission time per request in the saturating backlog cells (moves solves_per_s)."""
import layer


def read(ctx):
    return layer.admit_ms(ctx)
