"""One benchmark run of one cell: set-up, the measured window, the
comparison with the plain reference, and the result line.

Everything a cell needs is found by name: the cell in ``BENCHMARK.json``,
its configuration file, its traffic mix in ``bench/traffic/<name>.json``
and each per-layer metric's reader in ``bench/metrics/<name>.py``. A new
cell, mix or metric is a new file and a new entry; nothing here names one.

The service under test is ``repro.serving.SolveService``, driven through
its public calls: ``submit`` and ``poll`` (what ``stream`` calls per
request).

A cell on several chips runs the service on a 1-D mesh of the first
``chips`` devices. Its A is drawn split by rows over that mesh and never
held whole on one device or on the host (``problems.draw_sensors``), and
the reference runs the lanes of each A over the same mesh
(``reference.solve_shared``).
"""
from __future__ import annotations

import collections
import contextlib
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import time

import numpy as np

import layer as arith
import problems
import reference
import roofline
import trace_reduce
import traffic

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT_DIR = os.path.join("bench", ".out")

# the LC kernels as the device trace names them: the custom call of each
# Pallas entry point of kernels/amp_fused, named after its jitted wrapper;
# the row z-pass returns a tuple (z', sum of squares), the f-pass one array
KERNELS = {"z": r"^%amp_local_pallas_grid[.0-9]* = \(",
           "f": r"^%amp_local_pallas_grid[.0-9]* = [a-z]",
           "r": r"^%col_residual_pallas[.0-9]* = ",
           "inner": r"^%col_inner_pallas[.0-9]* = "}
SOLVE_MODULE = "solve_batch"      # the jitted het program of a bucket
TRACE_SECONDS = 5.0               # how much of a --trace 1 window is traced
SLICE_S = 5.0                     # stretch of the answers-per-slice report
MESH_AXIS = "data"                # the service's default ``mesh_axis``


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def now() -> float:
    return time.perf_counter()


# -- finding things by name -------------------------------------------------

def load_spec(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        return json.load(fh)


def find_cell(spec: dict, name: str) -> dict:
    for cell in spec["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def load_config(root: str, spec: dict, name: str) -> dict:
    for c in spec["configs"]:
        if c["name"] == name:
            with open(os.path.join(root, c["file"])) as fh:
                return json.load(fh)
    raise KeyError(f"no config {name!r} in BENCHMARK.json")


def _in_cell(metric: dict, cell: str, e2e_names=None) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return e2e_names is None or metric["moves"] in e2e_names


def cell_metrics(spec: dict, cell: str) -> tuple:
    """(end-to-end, per-layer) metric entries the cell reports."""
    e2e = [m for m in spec["end_to_end"] if _in_cell(m, cell)]
    names = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"] if _in_cell(m, cell, names)]
    return e2e, layer


def load_reader(root: str, name: str):
    """``read(ctx)`` of the per-layer metric ``name``."""
    path = os.path.join(root, "bench", "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# -- the service and its requests -------------------------------------------

def make_mesh(devs: list, chips: int):
    """The 1-D mesh of a cell on several chips, or None on one chip."""
    if chips <= 1:
        return None
    from jax.sharding import Mesh
    return Mesh(np.asarray(devs[:chips]), (MESH_AXIS,))


def build_service(cfg: dict, mesh=None):
    from repro.serving import BucketPolicy, SolveService
    sv = dict(cfg["service"])
    policy = BucketPolicy(**sv.pop("bucket_policy"))
    if mesh is not None:
        sv["mesh"] = mesh
    return SolveService(policy=policy, col_inner=cfg["col_inner"], **sv)


class Requests:
    """Builds ``SolveRequest``s from plan entries over the drawn data."""

    def __init__(self, cfg: dict, data: dict):
        from repro.core.denoisers import BernoulliGauss
        from repro.serving import SolveRequest
        self._req = SolveRequest
        self.cfg, self.data = cfg, data
        self.priors = [BernoulliGauss(eps=e, mu_s=cfg["mu_s"],
                                      sigma_s=cfg["sigma_s"])
                       for e in data["eps"]]
        self.iters = problems.sensor_iters(cfg)

    def make(self, s: int, key: str, k: int):
        """The request of plan entry (sensor, mix key, signal); a device
        A (a mesh cell's) goes into every request of its sensor as the
        one array object it is."""
        c = self.cfg
        policy, transport = traffic.split_key(key, c["transport"])
        kw = {}
        if policy == "dp":
            kw["dp_total_bits"] = c["dp_bits_per_iter"] * self.iters[s]
        return self._req(
            y=self.data["y"][s, k], a=self.data["a"][s], prior=self.priors[s],
            snr_db=c["snr_db"], n_proc=c["n_proc"], n_iter=self.iters[s],
            policy=policy, transport=transport,
            bt_c_ratio=c["bt"]["c_ratio"],
            bt_r_max=c["bt"]["r_max"], a_id=f"sensor{s}", **kw)


# -- the loops ----------------------------------------------------------------

class Log:
    """What a loop saw: per request its plan entry and times."""

    def __init__(self):
        self.plan: dict = {}        # request id -> (sensor, mix key, signal)
        self.done: dict = {}        # request id -> host time its result came
        self.results: dict = {}     # request id -> SolveResult
        self.admit: list = []       # (seconds in submit, dispatched a batch)


def _ann(on: bool, name: str):
    if not on:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)


def closed_loop(svc, plan, reqs: Requests, log: Log, stop, ann=False):
    """Saturating closed loop: submit the next request as soon as the
    previous call returned; ``stop(log, t)`` ends it after a poll."""
    while True:
        with _ann(ann, "bench.request"):
            s, pol, k = plan.next()
            req = reqs.make(s, pol, k)
        t_a = now()
        with _ann(ann, "bench.submit"):
            rid = svc.submit(req)
        t_b = now()
        log.plan[rid] = (s, pol, k)
        with _ann(ann, "bench.poll"):
            out = svc.poll()
        t_c = now()
        log.admit.append((t_b - t_a, bool(out)))
        for r in out:
            log.results[r.request_id] = r
            log.done[r.request_id] = t_c
        if stop(log, t_c):
            return t_c


class Tracer:
    """The profiler over the last ``seconds`` of the window (or all of
    it, if shorter): it starts at the first loop tick past ``t_on`` and
    stops when the window has closed, so the ``bench.window`` span and
    the device trace cover the same steady stretch, and writing the
    trace out falls after the window. A whole 30 s window of the row cell
    holds over a million device ops, more than the profiler keeps."""

    def __init__(self, log_dir: str, seconds: float):
        import jax
        self.jax, self.dir, self.seconds = jax, log_dir, seconds
        self.ann = None
        self.t_on = math.inf
        self.host_span = None       # (start, stop) on the harness clock

    def arm(self, t0: float, window_s: float) -> None:
        self.t_on = t0 + max(0.0, window_s - self.seconds)

    def tick(self, t: float) -> None:
        if self.ann is None and self.host_span is None and t >= self.t_on:
            self.start()

    def start(self) -> None:
        shutil.rmtree(self.dir, ignore_errors=True)
        opts = self.jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        self.jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.ann = self.jax.profiler.TraceAnnotation("bench.window")
        self.ann.__enter__()
        self.host_span = (now(), math.inf)

    def stop(self) -> None:
        if self.ann is not None:
            self.ann.__exit__(None, None, None)
            self.ann = None
            self.host_span = (self.host_span[0], now())
            self.jax.profiler.stop_trace()

    def read(self) -> dict | None:
        self.stop()
        if self.host_span is None:
            return None
        red = trace_reduce.reduce(
            trace_reduce.extract(trace_reduce.trace_file(self.dir)),
            KERNELS)
        red["host_span"] = self.host_span
        shutil.rmtree(self.dir, ignore_errors=True)
        return red


# -- comparison with the reference -----------------------------------------

def draw_sample(log: Log, ids: list, mix: dict, seed: int) -> list:
    """Request ids to compare: per mix key ``mix['compare'][key]`` of
    the answers due in the window, drawn from the seed, the longest
    request of each key always among them."""
    rng = np.random.default_rng([int(seed) % (1 << 64), 2])
    out = []
    for pol, count in sorted(mix["compare"].items()):
        cand = sorted(i for i in ids if log.plan[i][1] == pol)
        if not cand:
            continue
        longest = max(cand, key=lambda i: (log.results[i].deltas.shape[0], -i))
        rest = [i for i in cand if i != longest]
        pick = rng.choice(len(rest), size=min(count - 1, len(rest)),
                          replace=False) if count > 1 and rest else []
        out += [longest] + [rest[j] for j in sorted(pick)]
    return out


def reference_answers(cfg: dict, data: dict, cases: list,
                      precision: str, lanes: int = 8) -> dict:
    """Reference x for each (key, sensor, signal, T) case, ``lanes``
    problems per call. A host A (one chip): on the default device, in
    groups of one T. Device arrays of A (a mesh): each sensor's lanes over
    its own A where it lies, in groups of one sensor and T."""
    import jax
    import jax.numpy as jnp
    if not isinstance(data["a"], np.ndarray):
        return _shared_answers(cfg, data, cases, precision, lanes)
    a_dev = jnp.asarray(data["a"])
    eps = np.asarray(data["eps"], np.float32)
    out = {}
    by_t: dict = {}
    for c in cases:
        by_t.setdefault(c[3], []).append(c)
    for t, group in sorted(by_t.items()):
        for i in range(0, len(group), lanes):
            chunk = group[i:i + lanes]
            pad = chunk + [chunk[-1]] * (lanes - len(chunk))
            idx = np.asarray([c[1] for c in pad])
            ys = np.stack([data["y"][c[1], c[2]] for c in pad])
            x = reference.solve(a_dev[idx], ys, eps[idx], t,
                                mu=cfg["mu_s"], sigma=cfg["sigma_s"],
                                precision=precision)
            x = np.asarray(jax.device_get(x))
            for j, c in enumerate(chunk):
                out[c[0]] = x[j]
    del a_dev
    return out


def _shared_answers(cfg: dict, data: dict, cases: list, precision: str,
                    lanes: int) -> dict:
    import jax
    out = {}
    groups: dict = {}
    for c in cases:
        groups.setdefault((c[1], c[3]), []).append(c)
    for (s, t), group in sorted(groups.items()):
        for i in range(0, len(group), lanes):
            chunk = group[i:i + lanes]
            pad = chunk + [chunk[-1]] * (lanes - len(chunk))
            ys = np.stack([data["y"][s, c[2]] for c in pad])
            x = reference.solve_shared(data["a"][s], ys, data["eps"][s], t,
                                       mu=cfg["mu_s"], sigma=cfg["sigma_s"],
                                       precision=precision)
            x = np.asarray(jax.device_get(x))
            for j, c in enumerate(chunk):
                out[c[0]] = x[j]
    return out


def compare(cfg: dict, data: dict, log: Log, sample: list,
            answers: dict, x_ref: dict) -> dict:
    """Per kind of answer, the worst reading over the sampled answers,
    and how many answers passed their limit. Exact answers (lossless
    policy, ECSQ transport: exact fusion): mean squared difference to
    the reference over the reference's own MSE. Lossy answers (a rate
    policy, or a quantizing transport such as int8 psum): SDR loss
    against the reference, in dB."""
    lim = cfg["limits"]
    worst = {"lossless_msd_rel": None, "lossy_loss_db": None}
    bad = 0
    for rid in sample:
        s, key, k = log.plan[rid]
        s0 = data["s0"][s, k].astype(np.float64)
        xr = x_ref[rid].astype(np.float64)
        x = np.asarray(answers[rid], np.float64)
        mse_ref = max(float(np.mean((xr - s0) ** 2)), 1e-30)
        if traffic.split_key(key, cfg["transport"]) == ("lossless", "ecsq"):
            name = "lossless_msd_rel"
            v = float(np.mean((x - xr) ** 2)) / mse_ref
        else:
            name = "lossy_loss_db"
            v = 10.0 * math.log10(max(float(np.mean((x - s0) ** 2)), 1e-30)
                                  / mse_ref)
        if not math.isfinite(v) or v > lim[name]:
            bad += 1
            v = v if math.isfinite(v) else float("inf")
        worst[name] = v if worst[name] is None else max(worst[name], v)
    return {"worst": worst, "bad": bad}


# -- one run --------------------------------------------------------------------

def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             root: str = ROOT, spec: dict | None = None,
             t_start: float | None = None, require_chip: bool = True,
             control: bool = False) -> dict:
    """Run one cell once; returns the result dict (the printed line).
    ``control`` also reads the control: the reference one precision step
    below, put in the program's place (``result['control']``)."""
    t_start = now() if t_start is None else t_start
    say = lambda msg: print(f"[bench] {msg}", file=sys.stderr, flush=True)
    spec = load_spec(root) if spec is None else spec
    cell = find_cell(spec, workload)
    cfg = load_config(root, spec, cell["config"])
    mix = traffic.load(root, cell["traffic"])
    e2e, layer = cell_metrics(spec, workload)

    import jax
    devs = jax.devices()
    dev = devs[0]
    if require_chip and (dev.platform != "tpu" or len(devs) < cell["chips"]):
        raise NoChip(f"cell {workload} needs {cell['chips']} TPU chip(s); "
                     f"JAX found {len(devs)} {dev.platform} device(s)")
    say(f"{workload} seed {seed} on {len(devs)} x {dev.device_kind}, "
        f"backend up at {now() - t_start:.2f}s")

    mesh = make_mesh(devs, cell["chips"])
    data = problems.draw_sensors(cfg, mix["signals_per_sensor"], seed,
                                 mesh=mesh)
    reqs = Requests(cfg, data)
    svc = build_service(cfg, mesh)
    t_of = sorted(set(reqs.iters))
    say(f"data drawn at {now() - t_start:.2f}s")

    # warm-up: the cell's own traffic from another stream of the seed,
    # until every sensor's operands are resident and every horizon T has
    # run two batches (its programs compiled or loaded from the cache).
    # On a mesh, where a large request runs alone and its program follows
    # its policy and transport, also until every (T, mix key) has two
    # answers.
    warm = Log()
    batches: dict = {}
    answered: collections.Counter = collections.Counter()
    keys_due = ({(t, key) for t in t_of for key in mix["policies"]}
                if mesh is not None else set())

    def warmed(lg, _t):
        for r in lg.results.values():
            batches.setdefault(r.deltas.shape[0], set()).add(
                (r.bucket, lg.done[r.request_id]))
            s, key, _ = lg.plan[r.request_id]
            answered[(reqs.iters[s], key)] += 1
        lg.results.clear()
        seen = {lg.plan[i][0] for i in lg.plan}
        return (len(seen) == cfg["sensors"]
                and all(len(batches.get(t, ())) >= 2 for t in t_of)
                and all(answered[tk] >= 2 for tk in keys_due))

    warm_plan = traffic.Plan(mix, cfg["sensors"], seed, stream=1)
    closed_loop(svc, warm_plan, reqs, warm, warmed)
    say(f"warm: {len(warm.plan)} requests, {svc.compile_count()} programs")

    tracer = (Tracer(os.path.join(root, OUT_DIR, f"trace-{os.getpid()}"),
                     TRACE_SECONDS) if trace else None)
    tick = tracer.tick if trace else (lambda _t: None)

    # -- the measured window ------------------------------------------------
    log = Log()
    log.plan.update(warm.plan)
    plan = traffic.Plan(mix, cfg["sensors"], seed, stream=0)
    cc0 = svc.compile_count()
    t0 = now()
    setup_s = t0 - t_start
    if trace:
        tracer.arm(t0, seconds)

    def stop(_lg, t):
        tick(t)
        return t >= t0 + seconds
    t1 = closed_loop(svc, plan, reqs, log, stop, ann=trace)
    compiles = svc.compile_count() - cc0
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    reduced = tracer.read() if trace else None
    window_s = t1 - t0
    say(f"window {window_s:.3f}s: {len(log.results)} answers, "
        f"{compiles} compiles, peak {peak}")

    # answers due in the window: every request not answered before the
    # window whose bucket (its T) has answered a later request in it
    last: dict = {}
    for i in log.results:
        t = reqs.iters[log.plan[i][0]]
        last[t] = max(last.get(t, -1), i)
    due = sorted(i for i, (s, _, _) in log.plan.items()
                 if i not in warm.done
                 and i <= last.get(reqs.iters[s], -1))
    missing = [i for i in due if i not in log.results]
    ids = [i for i in due if i in log.results]
    layouts = {log.results[i].bucket.layout for i in ids}
    placements = [log.results[i].bucket.placement for i in ids]
    slices = np.bincount(np.asarray(
        [int((log.done[i] - t0) // SLICE_S) for i in ids], int), minlength=1)
    say(f"answers per {SLICE_S:g} s of the window: {slices.tolist()}")
    sig = [(s, k) for s, _, k in log.plan.values()]
    say(f"requests that repeat a signal of the run: "
        f"{len(sig) - len(set(sig))} of {len(sig)}")

    ctx = {"cfg": cfg, "cell": cell, "mix": mix, "log": log, "ids": ids,
           "window_s": window_s, "trace": reduced,
           "peaks": (roofline.peaks(dev.device_kind)
                     if trace and dev.platform == "tpu" else None)}
    metrics = {}
    if not trace:
        for m in e2e:
            if m["name"] == "setup_s":
                v = setup_s
            elif m["name"] == "solves_per_s":
                v = len(ids) / window_s
            else:
                raise KeyError(f"end-to-end metric {m['name']!r}")
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        say(f"trace: {reduced['window_s']:.3f}s, LC launches "
            f"{ {k: v['count'] for k, v in reduced['kernels'].items()} }, "
            f"batches in it need {arith.expected_launches(ctx)} each")
        for m in layer:
            v = load_reader(root, m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    # -- correctness: the program's state freed, then the reference --------
    del svc
    gc.collect()
    sample = draw_sample(log, ids, mix, seed)
    cases = [(i, log.plan[i][0], log.plan[i][2],
              reqs.iters[log.plan[i][0]]) for i in sample]
    t_r = now()
    x_ref = reference_answers(cfg, data, cases, "highest")
    answers = {i: log.results[i].x for i in sample}
    cmp = compare(cfg, data, log, sample, answers, x_ref)
    say(f"reference: {len(sample)} answers in {now() - t_r:.2f}s")
    lim = cfg["limits"]
    checks = {
        "lossless_msd_rel": [cmp["worst"]["lossless_msd_rel"],
                             lim["lossless_msd_rel"]],
        "lossy_loss_db": [cmp["worst"]["lossy_loss_db"],
                          lim["lossy_loss_db"]],
        "compiles_in_window": [compiles, 0],
        "answers_missing": [len(missing), 0],
        "wrong_layout": [len(layouts - {cfg["layout"]}), 0],
        "wrong_placement": [
            sum(p != cfg["placement"] for p in placements)
            if "placement" in cfg else None, 0],
    }
    checks = {k: v for k, v in checks.items() if v[0] is not None}
    failed = cmp["bad"] + len(missing)
    correct = (failed == 0 and len(sample) > 0
               and all(v <= lim_ for v, lim_ in checks.values()))
    result = {
        "correct": bool(correct), "attempted": len(due), "failed": failed,
        "metrics": metrics,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devs), "memory_peak_bytes": int(peak)},
    }
    if trace and reduced and reduced["devices"]:
        result["device"]["busy_s"] = reduced["busy_s"]
        result["device"]["window_s"] = reduced["window_s"]
        result["breakdown"] = reduced["breakdown"]
    if control:
        xc = reference_answers(cfg, data, cases, "bf16x3")
        cc = compare(cfg, data, log, sample, xc, x_ref)
        result["control"] = {
            "lossless_msd_rel": cc["worst"]["lossless_msd_rel"],
            "lossy_loss_db": cc["worst"]["lossy_loss_db"],
            "correct": cc["bad"] == 0}
    result["checks"] = {k: {"value": v, "limit": l}
                        for k, (v, l) in checks.items()}
    for k, (v, l) in checks.items():
        print(f"check {k} {v!r} limit {l!r}", file=sys.stderr, flush=True)
    return result
