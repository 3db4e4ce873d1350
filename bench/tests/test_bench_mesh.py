"""The harness on a mesh of four fake CPU devices: A drawn split by rows
and never whole on one device, the shared-A reference over it, and a
whole run of a tiny processor-sharded cell, sound and with the exchange
between devices left out."""
import os

import _mesh
import _paths

DRAW = '''
import json
import numpy as np
import harness, problems
import jax
from jax.sharding import PartitionSpec
cfg = json.load(open(CFG))
mesh = harness.make_mesh(jax.devices(), 4)
one = problems.draw_sensors(cfg, 8, SEED)
got = problems.draw_sensors(cfg, 8, SEED, mesh=mesh)
out = {"a_is_tuple": isinstance(got["a"], tuple), "a": [], "shards": []}
for s, a in enumerate(got["a"]):
    out["a"].append(bool(isinstance(a, jax.Array)
                         and a.sharding.spec == PartitionSpec("data", None)
                         and np.array_equal(np.asarray(a), one["a"][s])))
    out["shards"].append(sorted((str(sh.device), sh.data.shape)
                                for sh in a.addressable_shards))
out["s0"] = bool(np.array_equal(got["s0"], one["s0"]))
out["y_gap"] = float(np.max(np.abs(got["y"] - one["y"]))
                     / np.max(np.abs(one["y"])))
print(json.dumps(out))
'''

REFERENCE = '''
import json
import numpy as np
import harness, problems, reference
import jax
cfg = json.load(open(CFG))
mesh = harness.make_mesh(jax.devices(), 4)
data = problems.draw_sensors(cfg, 8, SEED, mesh=mesh)
s, t, eps = 1, 12, data["eps"][1]
a_host = np.asarray(data["a"][s])
want = np.asarray(reference.solve(np.stack([a_host] * 8), data["y"][s],
                                  np.full(8, eps), t))
gap = lambda x: float(np.linalg.norm(x - want) / np.linalg.norm(want))
out = {}
for prec in ("highest", "bf16x3"):
    x = reference.solve_shared(data["a"][s], data["y"][s], eps, t,
                               precision=prec)
    out[prec] = gap(np.asarray(x))
print(json.dumps(out))
'''

RUN = '''
import json
import harness
seen = {}
compare = harness.compare

def spy(cfg, data, log, sample, answers, x_ref):
    seen["placements"] = sorted({r.bucket.placement
                                 for r in log.results.values()})
    seen["by_key"] = {}
    for i in sample:
        one = compare(cfg, data, log, [i], answers, x_ref)["worst"]
        key = log.plan[i][1]
        kinds = seen["by_key"].setdefault(key, [])
        kinds += [k for k, v in one.items() if v is not None
                  and k not in kinds]
        seen.setdefault("transports", {}).setdefault(
            key, log.results[i].bucket.transport)
    return compare(cfg, data, log, sample, answers, x_ref)

harness.compare = spy
if FAULT:
    import faults
    faults.FAULTS[FAULT](setattr)
res = harness.run_cell(CELL, SEED, 0.5, False, root=ROOT,
                       require_chip=False)
print(json.dumps({"correct": res["correct"], "checks": res["checks"],
                  "attempted": res["attempted"], "count":
                  res["device"]["count"], **seen}))
'''


CFG = os.path.join(_paths.BENCH, "tests", "data", "tiny_proc.json")


def _run_cell(tmp_path, fault):
    return _mesh.run(RUN, timeout=180, CELL=_mesh.CELL, SEED=_mesh.SEED,
                     ROOT=_mesh.cell_root(tmp_path), FAULT=fault)


def test_sharded_draw_is_the_one_device_draw():
    out = _mesh.run(DRAW, CFG=CFG, SEED=_mesh.SEED)
    assert out["a_is_tuple"] and all(out["a"]), out
    for shards in out["shards"]:
        # four devices, a quarter of A's rows each: none holds all of it
        assert len({d for d, _ in shards}) == _mesh.N_DEV
        assert {tuple(s) for _, s in shards} == {(512 // 4, 1024)}
    assert out["s0"]
    # y is A s0 summed over fewer rows per device: float32 rounding apart
    assert out["y_gap"] < 1e-6, out


def test_shared_reference_over_the_mesh_matches_solve():
    out = _mesh.run(REFERENCE, CFG=CFG, SEED=_mesh.SEED)
    assert out["highest"] < 1e-6, out
    assert out["bf16x3"] > 3e-6 and out["bf16x3"] > 10 * out["highest"], out


def test_processor_sharded_cell_runs_correct(tmp_path):
    out = _run_cell(tmp_path, None)
    assert out["correct"], out
    assert out["count"] == _mesh.N_DEV and out["attempted"] > 0
    assert out["placements"] == ["proc"]
    assert out["checks"]["wrong_placement"] == {"value": 0, "limit": 0}
    # int8 on the wire is held to the SDR loss, exact fusion to exactness
    assert out["transports"] == {"bt": "ecsq", "lossless": "ecsq",
                                 "lossless/block8": "block8"}
    assert out["by_key"] == {"bt": ["lossy_loss_db"],
                             "lossless": ["lossless_msd_rel"],
                             "lossless/block8": ["lossy_loss_db"]}


def test_exchange_left_out_on_the_mesh_makes_run_incorrect(tmp_path):
    out = _run_cell(tmp_path, "mesh_exchange_left_out")
    assert out["correct"] is False, out
    assert out["placements"] == ["proc"]
