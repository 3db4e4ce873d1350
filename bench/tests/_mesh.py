"""Runs on a mesh of fake CPU devices, each in a fresh process (JAX fixes
its device count when it starts), and a root holding a tiny
processor-sharded cell (``data/tiny_proc.json`` under the mix
``data/mesh_mix.json``) for ``harness.run_cell``."""
import json
import os
import shutil
import subprocess
import sys

import _paths
import harness

N_DEV = 4
CELL = "tiny_proc.mesh_mix"
SEED = 2**35 + 91


def run(code: str, timeout: int = 120, **names) -> dict:
    """Run ``code`` on ``N_DEV`` CPU devices, each of ``names`` bound to
    its value first; its last line of output, parsed as JSON."""
    code = "".join(f"{k} = {v!r}\n" for k, v in names.items()) + code
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={N_DEV}"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(_paths.BENCH, "tests"), _paths.BENCH,
         os.path.join(_paths.ROOT, "src")])
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=timeout)
    if out.returncode != 0:
        raise AssertionError(f"mesh run failed:\n{out.stdout}\n{out.stderr}")
    return json.loads(out.stdout.strip().splitlines()[-1])


def cell_root(tmp) -> str:
    """A checkout-like root whose ``BENCHMARK.json`` holds the one
    four-device cell ``CELL``."""
    data = os.path.join(_paths.BENCH, "tests", "data")
    for sub, name in (("configs", "tiny_proc"), ("traffic", "mesh_mix")):
        os.makedirs(os.path.join(tmp, "bench", sub))
        shutil.copy(os.path.join(data, f"{name}.json"),
                    os.path.join(tmp, "bench", sub))
    real = harness.load_spec(_paths.ROOT)
    e2e = [dict(m, workloads=[CELL]) if "workloads" in m else m
           for m in real["end_to_end"]]
    spec = {"configs": [{"name": "tiny_proc", "source": "test",
                         "file": "bench/configs/tiny_proc.json",
                         "reduced": [], "why": "test"}],
            "workloads": [{"name": CELL, "config": "tiny_proc",
                           "traffic": "mesh_mix", "chips": N_DEV,
                           "why": "test"}],
            "end_to_end": e2e, "per_layer": []}
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as fh:
        json.dump(spec, fh)
    return str(tmp)
