"""The LC kernel suite compiles for a TPU v5e — without a chip.

JAX's TPU compiler is installed with jax; it compiles for a *described*
v5e (``topologies.get_topology_desc``) that is not attached, and raises
what the chip's compiler would raise: a block shape off the (8, 128)
tiling, a scalar stored to VMEM, more VMEM than a kernel may use. Interpret
mode (the CPU parity tests) checks none of that. Each test compiles one
kernel at the width the serving path runs it, about a second each.

The topology is described inside a module fixture — never at import, in
a ``skipif`` or in ``parametrize`` — so every xdist worker collects the
same tests and only the worker that runs this file loads the TPU library.
"""
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.amp_fused import col
from repro.kernels.amp_fused.amp_fused import BLOCK_BYTES
from repro.kernels.amp_fused.ops import (amp_local_grid, col_inner_step,
                                         col_residual, col_tiles,
                                         pad_row_shards, row_tiles)
from repro.serving.buckets import BucketPolicy, placement_for, round_up

# the paper's Sec. 4 operating point: N=10,000, M=3,000, P=30 -> Mp=100
PAPER = {"p": 30, "mp": 100, "n": 10_000}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent
    # cache but never read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, one_chip, *shapes):
    """Compile ``fn`` for the described chip at ``shapes`` ((shape, dtype)
    pairs); returns the HLO text, which must carry the Mosaic kernel."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    txt = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in txt
    return txt


def _row_shapes(p, mp, n, a_dtype, batch=()):
    """Aligned (A, x, y, z, onsager) shapes of one row LC step, as the
    engine pads them (``pad_row_shards``, for the dtype A streams in)."""
    a = jax.ShapeDtypeStruct((p, mp, n), a_dtype)
    a_pad, _ = jax.eval_shape(lambda a: pad_row_shards(a, None), a)
    _, mpp, npp = a_pad.shape
    f32 = jnp.float32
    return [(batch + (p, mpp, npp), a_dtype), (batch + (npp,), f32),
            (batch + (p, mpp), f32), (batch + (p, mpp), f32), (batch, f32)]


@pytest.mark.parametrize("a_dtype", [jnp.float32, jnp.bfloat16])
def test_row_kernel_compiles_at_paper_point(one_chip, a_dtype):
    bm, bn = row_tiles(PAPER["mp"], PAPER["n"])
    assert (bm, bn) == (104, 2560)
    step = partial(amp_local_grid, n_proc=PAPER["p"], use_pallas=True)
    _compile(step, one_chip, *_row_shapes(PAPER["p"], PAPER["mp"],
                                          PAPER["n"], a_dtype))


@pytest.mark.parametrize("a_dtype", [jnp.float32, jnp.bfloat16])
def test_row_kernel_compiles_for_tall_proc_shard(one_chip, a_dtype):
    """A processor-sharded (``proc``) device's stack at N=120,000,
    M=36,000, P=40 over four chips: 10 processors of 1,024 rows (the
    912-row bucket in two 512-row tiles, the tallest row tile). The byte
    budget narrows its N tile, and the kernel still fits the default
    scoped VMEM."""
    itemsize = jnp.dtype(a_dtype).itemsize
    bm, bn = row_tiles(1024, 120_000, itemsize)
    assert bm == 512 and bm * bn * itemsize <= BLOCK_BYTES
    step = partial(amp_local_grid, n_proc=40, use_pallas=True)
    _compile(step, one_chip, *_row_shapes(10, 1024, 120_000, a_dtype))


def test_row_kernel_compiles_vmapped_batch(one_chip):
    """One B=16 serving batch at the paper point's bucket shape (Mp pads
    to the service's 16-row quantum, N to 256): one launch, batch axis
    prepended to the grid by the vmap rule."""
    mp_bucket = round_up(PAPER["mp"], BucketPolicy().mp_quantum)
    step = jax.vmap(partial(amp_local_grid, n_proc=PAPER["p"],
                            use_pallas=True))
    _compile(step, one_chip, *_row_shapes(PAPER["p"], mp_bucket, 10_240,
                                          jnp.float32, batch=(16,)))


def _col_shapes(p, m, np_):
    f32 = jnp.float32
    mp = -(-m // col_tiles(m)) * col_tiles(m)
    return mp, [((p, mp, np_), f32), ((p, np_), f32), ((p, np_), f32),
                ((p, mp), f32), ((mp,), f32), ((np_,), f32),
                ((), f32), ((), f32), ((), f32), ((), f32)]


@pytest.mark.parametrize("np_", [3000, col.COL_NP_MAX])
def test_col_residual_kernel_compiles(one_chip, np_):
    _, shapes = _col_shapes(4, 3000, np_)
    _compile(partial(col_residual, use_pallas=True), one_chip, *shapes[:2])


@pytest.mark.parametrize("update_z", [True, False])
@pytest.mark.parametrize("np_", [3000, col.COL_NP_MAX])
def test_col_inner_kernel_compiles(one_chip, np_, update_z):
    _, shapes = _col_shapes(4, 3000, np_)
    _compile(partial(col_inner_step, update_z=update_z, use_pallas=True),
             one_chip, *shapes)


def test_col_np_bound_is_the_widest_that_compiles(one_chip, monkeypatch):
    """``COL_NP_MAX`` is tight: one more lane tile of columns overflows the
    default scoped VMEM of the inner kernel (so the bound must move with
    the kernel's VMEM use, in this test)."""
    bound = col.COL_NP_MAX
    monkeypatch.setattr(col, "COL_NP_MAX", 1 << 30)
    _, shapes = _col_shapes(4, 3000, bound + 128)
    with pytest.raises(Exception, match="vmem"):
        _compile(partial(col_inner_step, update_z=True, use_pallas=True),
                 one_chip, *shapes)


def test_col_width_above_bound_is_refused():
    """Column dispatch refuses a slice wider than the kernels compile for
    (never a silent jnp fallback), and the router keeps such requests on
    the row layout."""
    wide = col.COL_NP_MAX + 16
    a = jnp.zeros((1, 8, wide), jnp.float32)
    with pytest.raises(ValueError, match="COL_NP_MAX"):
        col_residual(a, jnp.zeros((1, wide)), use_pallas=True,
                     interpret=True)
    policy = BucketPolicy()
    assert placement_for(4 * col.COL_NP_MAX, 1000, 4, 1, policy) \
        == ("local", "col")
    assert placement_for(4 * wide, 1000, 4, 1, policy) == ("local", "row")
