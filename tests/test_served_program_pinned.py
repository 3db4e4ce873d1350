"""The served program is pinned: at a tiny row and a tiny column bucket, a
fixed-seed B=2 batch of one BT request and one lossless request runs the
het program the service dispatches (jnp path), and its answers hash to the
digests recorded before the engine's entry points were folded onto it.
A refactor that changes what the service computes changes a digest."""
import hashlib
import json

import jax
import numpy as np
import pytest

from repro.core.amp import sample_problem
from repro.core.denoisers import BernoulliGauss
from repro.core.state_evolution import CSProblem
from repro.serving import BucketPolicy, SolveRequest, SolveService

# layout -> (n, m, P, T, seed)
CASES = {"row": (128, 48, 4, 6, 11), "col": (256, 64, 4, 6, 12)}

PINNED = {
    "col": {
        "x":
            "30e2d34c0e6c6f473e2dd08d5f3b989e2e7b5ae5bafd3fc1311871bbc59956ec",
        "sigma2_hat":
            "ab327f52426b45d96befb382b8dd52a9c5719ee4ea958a846b245d79d48e7685",
        "deltas":
            "e0c3e146de980f5cc8768e2e421606e22838db4aa7867429c7ebd379ff6711a6",
    },
    "row": {
        "x":
            "a19a4f5ab07ea662c9d8c8468588af4b5fd231df7a0c7a4261a45cebcb2f8513",
        "sigma2_hat":
            "4ba12958b6d2f77644accb5ba14f55a65e15de1548dd7e2a24b04021985cc890",
        "deltas":
            "15cd03fdca57282d1710b556b499bc91397ca8c8068c0ecd76d64b50f3dd0c9e",
    },
}


def digests(layout: str) -> dict:
    n, m, p, t, seed = CASES[layout]
    prior = BernoulliGauss(eps=0.1)
    prob = CSProblem(n=n, m=m, prior=prior, snr_db=20.0)
    _, a, y = sample_problem(jax.random.PRNGKey(seed), n, m, prior,
                             prob.sigma_e2)
    svc = SolveService(policy=BucketPolicy(max_batch=2), use_kernel=False)
    reqs = [SolveRequest(y=y, a=a, prior=prior, n_proc=p, n_iter=t,
                         policy=pol, layout=layout)
            for pol in ("bt", "lossless")]
    reqs = [svc._prepare(r, assign_id=False) for r in reqs]
    key = svc._key_for(reqs[0])
    a_b, y_b, params, has_bt = svc._het_operands(key, reqs, use_cache=False)
    eng = svc._engine(key)
    trace = eng.trace_of(eng.dispatch_het(a_b, y_b, params, has_bt=has_bt))
    out = {}
    for name in ("x", "sigma2_hat", "deltas"):
        v = np.ascontiguousarray(getattr(trace, name))
        h = hashlib.sha256(repr((v.shape, str(v.dtype))).encode())
        h.update(v.tobytes())
        out[name] = h.hexdigest()
    return out


@pytest.mark.parametrize("layout", sorted(CASES))
def test_served_het_program_is_pinned(layout):
    assert digests(layout) == PINNED[layout]


if __name__ == "__main__":
    print(json.dumps({k: digests(k) for k in sorted(CASES)}, indent=1))
