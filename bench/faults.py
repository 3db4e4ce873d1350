"""Faults a cell can have, planted in the program under the timed path.

Each fault is ``plant(patch)``, where ``patch(obj, name, value)`` sets an
attribute (``setattr``, or pytest's ``monkeypatch.setattr``, which undoes
it). The CPU tests plant them at a small size and see ``correct`` come out
false; ``bench/control.py --fault`` plants one at a cell's own size on the
chip and prints what each compared number reads under it. A fault of
``MESH_ONLY`` breaks code that runs only on a mesh of several chips, so
a one-chip cell cannot have it.
"""
from __future__ import annotations

import dataclasses

import numpy as np

# how much wider than the BT controller chose a broken controller's bins are
BT_BIN_FACTOR = 8.0


def _engine():
    from repro.core import engine
    return engine


def _trace_with_x(change):
    eng = _engine()
    orig = eng.AmpEngine._trace

    def patched(self, x, outs):
        tr = orig(self, x, outs)
        return dataclasses.replace(tr, x=change(tr.x))
    return patched


def state_unchanged(patch):
    """The solve hands back its initial state, x = 0."""
    patch(_engine().AmpEngine, "_trace", _trace_with_x(np.zeros_like))


def answer_altered(patch):
    """One entry of every answer is off by 1 where the answer is made."""
    def alter(x):
        x = x.copy()
        x[..., 0] += 1.0
        return x
    patch(_engine().AmpEngine, "_trace", _trace_with_x(alter))


def half_batch_dropped(patch):
    """Each batch hands back only its first half of answers."""
    from repro.serving import service
    orig = service.SolveService._dispatch_bucket

    def patched(self, key, reqs):
        fin = orig(self, key, reqs)
        return lambda: (lambda out: out[:max(1, len(out) // 2)])(fin())
    patch(service.SolveService, "_dispatch_bucket", patched)


def exchange_left_out(patch):
    """Lossy fusion without the exchange: processor 0's message stands
    for every processor's."""
    eng = _engine()
    orig = eng.EcsqTransport.fuse

    def patched(self, f_p, delta, drop=None):
        f, extra, q = orig(self, f_p, delta, drop)
        return f_p.shape[0] * f_p[0], extra, q
    patch(eng.EcsqTransport, "fuse", patched)


def mesh_exchange_left_out(patch):
    """Fusion on the mesh without the exchange between chips: each device
    stands its own processors' sum, times the device count, in for the
    psum over the mesh, exact (``PsumFusion``) or int8/int4
    (``CompressedPsumTransport``)."""
    from jax import lax
    eng = _engine()

    def psum_fuse(self, f_p, delta, drop):
        f_loc, extra_loc, q = self.local.fuse(f_p, delta)
        n_dev = lax.axis_size(self.axis)
        return n_dev * f_loc, n_dev * extra_loc, q

    orig = eng.CompressedPsumTransport.fuse

    def compressed_fuse(self, f_p, delta, drop):
        _, noise, q = orig(self, f_p, delta, drop)
        return lax.axis_size(self.axis) * f_p.sum(axis=0), noise, q
    patch(eng.PsumFusion, "fuse", psum_fuse)
    patch(eng.CompressedPsumTransport, "fuse", compressed_fuse)


def bt_bins_coarse(patch):
    """Both BT controllers (row and column) quantize with bins
    ``BT_BIN_FACTOR`` times wider than the ones they chose, three bits
    fewer per entry at the default factor."""
    eng = _engine()
    for name in ("bt_delta_for", "col_bt_delta_for"):
        orig = getattr(eng, name)

        def patched(tb, t, v, _orig=orig):
            delta, rate = _orig(tb, t, v)
            return delta * BT_BIN_FACTOR, rate
        patch(eng, name, patched)


FAULTS = {"state_unchanged": state_unchanged,
          "half_batch_dropped": half_batch_dropped,
          "exchange_left_out": exchange_left_out,
          "answer_altered": answer_altered,
          "bt_bins_coarse": bt_bins_coarse,
          "mesh_exchange_left_out": mesh_exchange_left_out}
MESH_ONLY = ("mesh_exchange_left_out",)
