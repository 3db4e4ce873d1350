"""A new configuration, traffic mix, cell and per-layer metric are picked
up by name: new files and new entries, no edit of the harness."""
import json
import os
import shutil

import _paths  # noqa: F401
import _tiny
import harness

NEW_METRIC = '''"""Requests admitted in the window (a count, for this test)."""


def read(ctx):
    return float(len(ctx["log"].admit))
'''


def test_new_files_are_found_by_name(tmp_path):
    root = tmp_path
    for d in ("configs", "traffic", "metrics"):
        os.makedirs(root / "bench" / d)
    cfg = json.load(open(os.path.join(_paths.BENCH, "tests", "data",
                                      "tiny_row.json")))
    cfg.update(name="tiny_new", eps=[0.05], n_iter=[8], sensors=3)
    json.dump(cfg, open(root / "bench" / "configs" / "tiny_new.json", "w"))
    mix = {"kind": "closed", "popularity": "rounds",
           "policies": {"lossless": 1}, "signals_per_sensor": 2,
           "compare": {"lossless": 3}}
    json.dump(mix, open(root / "bench" / "traffic" / "lossless_only.json",
                        "w"))
    (root / "bench" / "metrics" / "admitted.new.py").write_text(NEW_METRIC)
    shutil.copy(os.path.join(_paths.BENCH, "metrics",
                             "admit_ms.backlog.py"),
                root / "bench" / "metrics")

    spec = _tiny.spec()
    spec["configs"].append({"name": "tiny_new", "source": "test",
                            "file": "bench/configs/tiny_new.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tiny_new.lossless_only",
                              "config": "tiny_new",
                              "traffic": "lossless_only", "chips": 1,
                              "why": "test"})
    spec["end_to_end"][0]["workloads"].append("tiny_new.lossless_only")
    spec["per_layer"].append({"name": "admitted.new", "unit": "requests",
                              "better": "higher", "source": "host_clock",
                              "layer": "service admission",
                              "moves": "solves_per_s"})
    for c in spec["configs"][:-1]:
        shutil.copy(os.path.join(_paths.ROOT, c["file"]),
                    root / "bench" / "configs")
        c["file"] = "bench/configs/" + os.path.basename(c["file"])
    json.dump(spec, open(root / "BENCHMARK.json", "w"))

    e2e, layer = harness.cell_metrics(harness.load_spec(str(root)),
                                      "tiny_new.lossless_only")
    assert {m["name"] for m in e2e} == {"solves_per_s", "setup_s"}
    assert [m["name"] for m in layer] == ["admitted.new"]

    res = harness.run_cell("tiny_new.lossless_only", 5, 0.3, True,
                           root=str(root), require_chip=False)
    assert res["correct"], res["checks"]
    assert res["metrics"]["admitted.new"]["value"] > 0
    assert res["metrics"]["admitted.new"]["unit"] == "requests"
