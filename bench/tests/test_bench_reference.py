"""The benchmark's plain reference matches a lossless solve of the
service at a small size; the control (three bfloat16 passes) does not."""
import numpy as np

import _paths  # noqa: F401
import problems
import reference
from repro.core.denoisers import BernoulliGauss
from repro.serving import SolveRequest, SolveService

CFG = {"n": 400, "m": 160, "n_proc": 4, "snr_db": 20.0, "eps": [0.05, 0.1],
       "n_iter": [10, 20], "mu_s": 0.0, "sigma_s": 1.0, "sensors": 4}


def _msd_rel(x, x_ref, s0):
    return float(np.mean((x - x_ref) ** 2) / np.mean((x_ref - s0) ** 2))


def test_reference_matches_a_lossless_service_solve():
    data = problems.draw_sensors(CFG, 2, seed=2**33 + 1)
    iters = problems.sensor_iters(CFG)
    svc = SolveService()
    for layout in ("row", "col"):
        reqs = [SolveRequest(y=data["y"][s, 1], a=data["a"][s],
                             prior=BernoulliGauss(eps=data["eps"][s]),
                             n_proc=4, n_iter=iters[s], policy="lossless",
                             layout=layout) for s in range(4)]
        got = svc.solve(reqs)
        for s, res in enumerate(got):
            args = (data["a"][s:s + 1], data["y"][s:s + 1, 1],
                    data["eps"][s:s + 1], iters[s])
            x_ref = np.asarray(reference.solve(*args))[0]
            x_ctl = np.asarray(reference.solve(*args, precision="bf16x3"))[0]
            s0 = data["s0"][s, 1]
            prog = _msd_rel(res.x, x_ref, s0)
            ctl = _msd_rel(x_ctl, x_ref, s0)
            assert res.bucket.layout == layout
            assert prog < 1e-9, (layout, s, prog)
            assert ctl > 10 * max(prog, 1e-12), (layout, s, prog, ctl)


def test_draw_repeats_for_one_seed():
    a = problems.draw_sensors(CFG, 2, seed=2**40 + 3)
    b = problems.draw_sensors(CFG, 2, seed=2**40 + 3)
    c = problems.draw_sensors(CFG, 2, seed=2**40 + 4)
    for k in ("a", "s0", "y"):
        assert np.array_equal(a[k], b[k])
        assert not np.array_equal(a[k], c[k])
    # the measurement model: A_ij ~ N(0, 1/M)
    assert abs(np.var(a["a"]) * CFG["m"] - 1.0) < 0.02
