"""Peaks of the chip and the bytes each LC kernel launch has to move.

The yardstick for every roofline share the benchmark reports. It counts
the work from the shapes of the padded bucket stack the kernels read, so
the same work is counted whatever implements it, and a share can only
pass 100% when the time leaves out part of the work.
"""
from __future__ import annotations

# Published per-chip peaks, keyed by ``jax.devices()[0].device_kind``.
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
}
PEAKS_SOURCE = ("Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
                "16 GB of HBM at 819 GB/s per chip")

F32 = 4


def peaks(device_kind: str) -> dict:
    """Peaks of one chip of ``device_kind``; an unknown kind is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"add them to bench/roofline.py with their source"
                       ) from None


def row_launch_bytes(kernel: str, batch: int, p: int, mp: int, n: int,
                     a_bytes: int = F32) -> float:
    """Least HBM bytes of one row LC kernel launch over a (batch, P, mp, n)
    stack: A once, plus the vectors the pass reads and writes.

    ``z``: z' = y - A x + b z per processor (reads A, x, y, z; writes z');
    ``f``: f = x/P + A^T z' per processor (reads A, z', x; writes f)."""
    a = p * mp * n * a_bytes
    if kernel == "z":
        vec = n + 3 * p * mp
    elif kernel == "f":
        vec = p * mp + n + p * n
    else:
        raise ValueError(kernel)
    return float(batch * (a + F32 * vec))


def col_launch_bytes(kernel: str, batch: int, p: int, m: int, np_: int,
                     a_bytes: int = F32) -> float:
    """Least HBM bytes of one column LC kernel launch over a (batch, P, m,
    np_) stack, A once per launch.

    ``r``: r_p = A_p x_p (reads A, x; writes r);
    ``inner``: the last inner iteration of a round (no residual update):
    f_p = x_p + A_p^T z_p, then the in-kernel denoiser (reads A, x, x0, z,
    g and the column mask; writes x')."""
    a = p * m * np_ * a_bytes
    if kernel == "r":
        vec = p * np_ + p * m
    elif kernel == "inner":
        vec = 3 * p * np_ + p * m + m + np_
    else:
        raise ValueError(kernel)
    return float(batch * (a + F32 * vec))
