"""Share of the drift tail's SE-prediction lookups that its memo served, from the counts on the service's drift spans, in the backlog cells (moves solves_per_s)."""
import phases


def read(ctx):
    counts = phases.span_counts(ctx, "drift")
    if counts is None:
        return None
    n = sum(c["lookups"] for c in counts)
    if n <= 0:
        return None
    return 100.0 * sum(c["lookups"] - c["misses"] for c in counts) / n
