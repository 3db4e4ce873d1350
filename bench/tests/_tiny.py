"""A CPU-sized benchmark spec for the tests: the committed cells, their
configurations swapped for the small stand-ins in ``data/``."""
import copy

import _paths
import harness

TINY = {"row_paper": "bench/tests/data/tiny_row.json",
        "col_paper": "bench/tests/data/tiny_col.json"}
SEED = 2**35 + 77


def spec():
    s = copy.deepcopy(harness.load_spec(_paths.ROOT))
    for c in s["configs"]:
        c["file"] = TINY[c["name"]]
    return s


def run(workload, seed=SEED, seconds=0.5, trace=False, **kw):
    return harness.run_cell(workload, seed, seconds, trace, spec=spec(),
                            require_chip=False, **kw)
