"""Roofline analysis over the dry-run artifacts (EXPERIMENTS.md §Roofline).

Terms per (arch x shape), single-pod 16x16 mesh (256 chips), TPU v5e:
    compute    = dot_FLOPs_per_device / 197e12        [s]
    memory     = HBM_bytes_per_device / 819e9         [s]
    collective = wire_bytes_per_device / 50e9         [s]
(dry-run quantities are per-device already — SPMD HLO shapes are local).

MODEL_FLOPS = 6*N*D (train) / 2*N*D (prefill) / 2*N*B (decode), N = active
params; the ratio MODEL_FLOPS/HLO_FLOPs exposes remat/dispatch overhead.
"""
from __future__ import annotations

import glob
import json
import os
import subprocess

from repro.configs import get_config, shape_for

# Published per-chip peaks, keyed by ``jax.devices()[0].device_kind``
# (a TPU v5e reports "TPU v5 lite"). Source: Google Cloud documentation,
# "TPU v5e": 197 TFLOP/s bf16, 819 GB/s of HBM bandwidth.
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bw": 819e9},
}


def device_peaks(device_kind: str) -> dict:
    """Peaks of one chip of ``device_kind``; a kind not in ``PEAKS`` is an
    error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to roofline.PEAKS "
                       f"with their source") from None


PEAK_FLOPS = PEAKS["TPU v5 lite"]["bf16_flops"]   # dry-run pod: v5e chips
HBM_BW = PEAKS["TPU v5 lite"]["hbm_bw"]
ICI_BW = 50e9         # B/s / link (conservative single-link)
CHIPS = 256


def git_commit() -> str:
    """Short HEAD hash for benchmark-JSON provenance, ``-dirty``-suffixed
    when the working tree has uncommitted changes — local pre-commit runs
    must stay distinguishable from CI post-commit runs in the archived
    trajectory. The tracked benchmark JSONs themselves are ignored by the
    dirtiness check (CI regenerates them in-place before uploading)."""
    cwd = os.path.dirname(os.path.abspath(__file__))
    try:
        head = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10, cwd=cwd,
        ).stdout.strip()
        if not head:
            return "unknown"
        status = subprocess.run(
            ["git", "status", "--porcelain"],
            capture_output=True, text=True, timeout=10, cwd=cwd,
        ).stdout.splitlines()
        dirty = [l for l in status
                 if not l.split()[-1].startswith("BENCH_")]
        return head + ("-dirty" if dirty else "")
    except Exception:
        return "unknown"


def lc_bytes(m: int, n: int, batch: int = 1, a_bytes: int = 4,
             vec_bytes: int = 4) -> float:
    """HBM bytes moved by one fused AMP LC step (either layout).

    The sensing operand dominates: both the row LC (z-pass + f-pass) and
    the column per-round step (residual pass + message pass) read A
    exactly twice — the information-theoretic minimum for the two
    contraction orders (DESIGN.md §8). Vector traffic (y, z in; z', f
    out; x in) is the small additive term. ``a_bytes=2`` models bf16
    A-streaming (``EngineConfig.a_dtype``).
    """
    a_traffic = 2.0 * m * n * a_bytes
    vec_traffic = (4.0 * m + 3.0 * n) * vec_bytes
    return batch * (a_traffic + vec_traffic)


def lc_roofline_seconds(m: int, n: int, batch: int = 1, a_bytes: int = 4,
                        bw: float = HBM_BW) -> float:
    """Memory-bound time floor for one LC step at bandwidth ``bw``."""
    return lc_bytes(m, n, batch, a_bytes) / bw


def model_flops_per_device(arch: str, shape_name: str) -> float:
    cfg = get_config(arch)
    shape = shape_for(shape_name)
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        total = 6.0 * n_active * shape.global_batch * shape.seq_len
    elif shape.kind == "prefill":
        total = 2.0 * n_active * shape.global_batch * shape.seq_len
    else:  # decode: one token per sequence
        total = 2.0 * n_active * shape.global_batch
    return total / CHIPS


def load_cells(dryrun_dir: str, mesh: str = "pod1") -> list[dict]:
    rows = []
    for path in sorted(glob.glob(os.path.join(dryrun_dir, f"*_{mesh}.json"))):
        with open(path) as f:
            rec = json.load(f)
        if rec.get("skipped"):
            rows.append({"arch": rec["arch"], "shape": rec["shape"],
                         "skipped": True, "reason": rec.get("reason", "")})
            continue
        if not rec.get("ok"):
            rows.append({"arch": rec["arch"], "shape": rec["shape"],
                         "failed": True, "error": rec.get("error", "")})
            continue
        compute = rec["dot_flops_per_device"] / PEAK_FLOPS
        memory = rec["hbm_bytes_per_device"] / HBM_BW
        coll = rec["wire_bytes_per_device"] / ICI_BW
        dominant = max(("compute", compute), ("memory", memory),
                       ("collective", coll), key=lambda kv: kv[1])
        mf = model_flops_per_device(rec["arch"], rec["shape"])
        rows.append({
            "arch": rec["arch"], "shape": rec["shape"],
            "compute_s": compute, "memory_s": memory, "collective_s": coll,
            "dominant": dominant[0],
            "bound_s": dominant[1],
            "roofline_frac": compute / dominant[1] if dominant[1] else 0.0,
            "model_flops_per_dev": mf,
            "useful_ratio": mf / rec["dot_flops_per_device"]
            if rec["dot_flops_per_device"] else 0.0,
            "memory_gb_per_dev": (rec["memory"].get("argument_bytes", 0)
                                  + rec["memory"].get("temp_bytes", 0)) / 2**30
            if isinstance(rec.get("memory"), dict) else None,
        })
    return rows


def format_table(rows: list[dict]) -> str:
    hdr = (f"{'arch':22s} {'shape':12s} {'compute':>9s} {'memory':>9s} "
           f"{'collect':>9s} {'bound':>10s} {'roofl%':>7s} {'useful%':>8s} "
           f"{'mem GB':>7s}")
    lines = [hdr, "-" * len(hdr)]
    for r in rows:
        if r.get("skipped"):
            lines.append(f"{r['arch']:22s} {r['shape']:12s} "
                         f"{'— skipped (' + r['reason'][:40] + ')':s}")
            continue
        if r.get("failed"):
            lines.append(f"{r['arch']:22s} {r['shape']:12s} FAILED: "
                         f"{r['error'][:60]}")
            continue
        lines.append(
            f"{r['arch']:22s} {r['shape']:12s} {r['compute_s']:9.4f} "
            f"{r['memory_s']:9.4f} {r['collective_s']:9.4f} "
            f"{r['dominant']:>10s} {100*r['roofline_frac']:6.1f}% "
            f"{100*min(r['useful_ratio'],9.99):7.1f}% "
            f"{r['memory_gb_per_dev']:7.2f}" if r.get("memory_gb_per_dev")
            is not None else
            f"{r['arch']:22s} {r['shape']:12s} (no memory data)")
    return "\n".join(lines)


def main(dryrun_dir: str = "results/dryrun"):
    rows = load_cells(dryrun_dir)
    print(format_table(rows))
    return rows


if __name__ == "__main__":
    main()
