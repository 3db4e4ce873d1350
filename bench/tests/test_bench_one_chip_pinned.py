"""The one-chip path of the harness is pinned: at the tiny row and column
configurations and a fixed seed, the drawn problems, the built requests
and the reference answers hash to the digests recorded on the commit
before the harness learned to run cells on a mesh."""
import dataclasses
import hashlib
import json
import os

import numpy as np
import pytest

import _paths
import harness
import problems
import traffic

SEED = 2**35 + 4242
REQUESTS = 24            # plan entries turned into requests
CASES = 6                # of them, answered by the reference

PINNED = {
    "tiny_col": {
        "a":
            "4408968c28b4845fba46aa43fdadd0634d78af10413215c5a9b925c23b7de259",
        "s0":
            "edce9cf1fe7572f0b4b176e942511deb5ae4c508364f823e886401ff37af7cd2",
        "y":
            "a34745e9889e9cd3fb0cad960ed0e1d68e9e0c9f5812da52a0dc5917f05b2d3d",
        "requests":
            "6ea64695c9f88eaf804bfc5bce6211784da7f2efd5d757d9d52fa205b959aa99",
        "reference.highest":
            "91809d9ef4b5c2bc1feae76ec1e6ffdab5349a444a1857b4d91819e9edde4af3",
        "reference.bf16x3":
            "04c46b79af4e11d20f1cebe8cc34c884f56966e64219ce2802f2d7911630bff9",
    },
    "tiny_row": {
        "a":
            "15064a89a501c40ea00c34b933088b072e972953e4d714ea8e142c39ac5ce680",
        "s0":
            "f3b80acca506f707aa90f81cd2e1edcc1ad97f37d62ceac33ff4a0700039bc9c",
        "y":
            "c0c8e9ab77be0c37c6f2f9049d1391e04fb513a85113279981926cea0b3d95f6",
        "requests":
            "d659d018c909d5c6b43f7f952e887fe09a1cfba21ec2017cdfc584c636e76154",
        "reference.highest":
            "8861968c6f2082a01128cc53d1db5fd797c004482e71ff86932633ecb6f6fc8e",
        "reference.bf16x3":
            "632cefef851e2160e4c1f0d8ceb13bdf574c10a01d43db699bee67de91f984a5",
    },
}


def _hash_array(h, v) -> None:
    v = np.ascontiguousarray(v)
    h.update(repr((v.shape, str(v.dtype))).encode())
    h.update(v.tobytes())


def _hash_request(h, req) -> None:
    for f in dataclasses.fields(req):
        v = getattr(req, f.name)
        h.update(f.name.encode())
        if isinstance(v, np.ndarray):
            _hash_array(h, v)
        else:
            h.update(repr(v).encode())


def digests(name: str) -> dict:
    """sha256 of the drawn arrays, of every field of the first requests
    of the committed mix's plan, and of the reference answers to some."""
    with open(os.path.join(_paths.BENCH, "tests", "data",
                           f"{name}.json")) as fh:
        cfg = json.load(fh)
    mix = traffic.load(_paths.ROOT, "bt_backlog")
    data = problems.draw_sensors(cfg, mix["signals_per_sensor"], SEED)
    out = {}
    for k in ("a", "s0", "y"):
        h = hashlib.sha256()
        _hash_array(h, data[k])
        out[k] = h.hexdigest()
    reqs = harness.Requests(cfg, data)
    plan = traffic.Plan(mix, cfg["sensors"], SEED, stream=0).take(REQUESTS)
    h = hashlib.sha256()
    for s, pol, k in plan:
        _hash_request(h, reqs.make(s, pol, k))
    out["requests"] = h.hexdigest()
    cases = [(i, s, k, reqs.iters[s])
             for i, (s, _, k) in enumerate(plan[:CASES])]
    for prec in ("highest", "bf16x3"):
        x = harness.reference_answers(cfg, data, cases, prec)
        h = hashlib.sha256()
        for i in sorted(x):
            _hash_array(h, x[i])
        out[f"reference.{prec}"] = h.hexdigest()
    return out


@pytest.mark.parametrize("name", sorted(PINNED))
def test_one_chip_draw_requests_and_reference_are_pinned(name):
    assert digests(name) == PINNED[name]


if __name__ == "__main__":
    print(json.dumps({n: digests(n) for n in sorted(PINNED)}, indent=1))
