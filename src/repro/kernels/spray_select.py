"""Pallas TPU kernel: batched Whack-a-Mole path selection.

The per-packet decision of the paper (§4) — bit-reverse the seeded counter
and search the cumulative profile — fused into one VPU pass:

    key  = shuffle(counter; sa, sb, ell, method)        (uint32 bit ops)
    path = sum_i [ c(i) <= key ]                         (branchless search)

The branchless sum-of-comparisons replaces binary search: for n paths it is
an [blk, n] broadcast-compare-reduce, which is how a searchsorted over a tiny
sorted array should look on a vector unit (no data-dependent control flow,
perfectly lane-parallel).  n is padded to the 128-lane boundary with the
sentinel m (never exceeded by a key), so padding lanes never count.

Block layout: counters are tiled [blk] in VMEM (blk = 1024 by default,
8 x 128 lanes); the cumulative array (padded to 128) is replicated per block.
The kernel is memory-bound: ~12 bytes moved per decision, a few dozen VPU ops
— matching the paper's 'low per-packet overhead suitable for NIC/GPU-resident
implementation', adapted to the TPU vector unit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.spray import SprayMethod

__all__ = ["spray_select_pallas", "PATH_PAD"]

PATH_PAD = 128  # lane-aligned padding for the cumulative array

# Plain int literals: pallas kernels must not capture traced constants.
_M1 = 0x55555555
_M2 = 0x33333333
_M4 = 0x0F0F0F0F
_M8 = 0x00FF00FF


def _bitrev32(x):
    x = ((x >> 1) & _M1) | ((x & _M1) << 1)
    x = ((x >> 2) & _M2) | ((x & _M2) << 2)
    x = ((x >> 4) & _M4) | ((x & _M4) << 4)
    x = ((x >> 8) & _M8) | ((x & _M8) << 8)
    return (x >> 16) | (x << 16)


def _theta(j, ell: int):
    mask = (1 << ell) - 1
    return _bitrev32(j & mask) >> (32 - ell)


def _kernel(counter_ref, c_ref, seed_ref, out_ref, *, ell: int, method: int):
    j = counter_ref[...]                       # uint32[blk]
    sa = seed_ref[0]
    sb = seed_ref[1]
    mask = jnp.uint32((1 << ell) - 1)
    if method == SprayMethod.PLAIN:
        key = _theta(j, ell)
    elif method == SprayMethod.SHUFFLE_1:
        key = _theta((sa + j * sb) & mask, ell)
    elif method == SprayMethod.SHUFFLE_2:
        key = (sa + sb * _theta(j, ell)) & mask
    else:
        raise ValueError(f"unknown method {method}")
    key_i = key.astype(jnp.int32)
    c = c_ref[...]                             # int32[PATH_PAD]
    # smallest i with key < c(i)  ==  #{i : c(i) <= key}
    out_ref[...] = jnp.sum(
        (c[None, :] <= key_i[:, None]).astype(jnp.int32), axis=1
    )


@functools.partial(
    jax.jit, static_argnames=("ell", "method", "block", "interpret")
)
def spray_select_pallas(
    counters: jax.Array,  # uint32[B]
    c: jax.Array,         # int32[n] inclusive cumulative profile
    sa,
    sb,
    *,
    ell: int,
    method: int,
    block: int = 1024,
    interpret: bool = False,
) -> jax.Array:
    """Batched path selection for any B >= 1.

    A batch that is not a multiple of `block` is zero-padded up to the next
    block boundary (the padding lanes compute throwaway selections that are
    sliced off) — the grid stays fully dense so the kernel body never needs
    a bounds mask.  `interpret=True` runs the kernel body through the Pallas
    interpreter (how the tests check it off the TPU); the default compiles
    it.
    """
    (B,) = counters.shape
    n = c.shape[0]
    if B == 0:
        raise ValueError("empty counter batch")
    if n > PATH_PAD:
        raise ValueError(f"at most {PATH_PAD} paths supported, got {n}")
    pad = -B % block
    if pad:
        counters = jnp.concatenate(
            [counters, jnp.zeros((pad,), counters.dtype)]
        )
    m = jnp.int32(1 << ell)
    c_pad = jnp.full((PATH_PAD,), m, jnp.int32).at[:n].set(c.astype(jnp.int32))
    seed = jnp.stack(
        [jnp.asarray(sa, jnp.uint32), jnp.asarray(sb, jnp.uint32)]
    )
    grid = ((B + pad) // block,)
    out = pl.pallas_call(
        functools.partial(_kernel, ell=ell, method=method),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((PATH_PAD,), lambda i: (0,)),
            pl.BlockSpec((2,), lambda i: (0,)),  # seed (sa, sb)
        ],
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((B + pad,), jnp.int32),
        interpret=interpret,
    )(counters, c_pad, seed)
    return out[:B] if pad else out
