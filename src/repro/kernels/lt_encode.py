"""Pallas TPU kernel: LT/fountain-code encoding (GF(2) XOR aggregation).

The paper's transport pairs spraying with erasure coding ("compatibility with
coding-based reliability such as fountain codes or LT3"): each encoded packet
is the XOR of a small set of source symbols, so ANY sufficiently large subset
of received packets decodes the message.  Encoding throughput is the compute
hot-spot of a coded sender — this kernel streams source payloads resident in
VMEM and produces encoded packets at VPU XOR rate.

Layout: payload [K, P] uint32 (K source symbols, P words each), neighbor
lists [R, dmax] int32 + validity mask (degree <= dmax).  The wrapper folds
the mask into the neighbor table (-1 marks an unused slot), and each grid
step gets its [block_r, dmax] slice of that table in SMEM, where the TPU
reads dynamic scalars.  The grid runs payload-column tiles (P) outermost
so one [K, block_p] payload tile stays resident in VMEM while every output
row tile (R) XORs its dmax dynamically indexed payload rows into its
[block_r, block_p] output tile.

dmax is static: the robust-soliton tail is clipped by the host (degrees
above dmax are re-sampled; see repro.net.fountain).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["lt_encode_pallas"]


def _kernel(sel_ref, payload_ref, out_ref, *, dmax: int, br: int):
    def xor_row(r, carry):
        def xor_one(t, acc):
            idx = sel_ref[r, t]
            row = payload_ref[pl.ds(jnp.maximum(idx, 0), 1), :]
            return acc ^ jnp.where(idx >= 0, row, jnp.uint32(0))

        acc = jax.lax.fori_loop(
            0, dmax, xor_one, jnp.zeros((1, out_ref.shape[1]), jnp.uint32)
        )
        out_ref[pl.ds(r, 1), :] = acc
        return carry

    jax.lax.fori_loop(0, br, xor_row, 0)


@functools.partial(
    jax.jit, static_argnames=("block_r", "block_p", "interpret")
)
def lt_encode_pallas(
    payload: jax.Array,    # uint32[K, P]
    neighbors: jax.Array,  # int32[R, dmax]
    valid: jax.Array,      # bool[R, dmax]
    *,
    block_r: int = 8,
    block_p: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """`interpret=True` runs the kernel body through the Pallas interpreter
    (how the tests check it off the TPU); the default compiles it."""
    K, P = payload.shape
    R, dmax = neighbors.shape
    if R % block_r != 0 or P % block_p != 0:
        raise ValueError(
            f"R={R} must tile by {block_r} and P={P} by {block_p}"
        )
    sel = jnp.where(valid, neighbors.astype(jnp.int32), -1)
    grid = (P // block_p, R // block_r)
    return pl.pallas_call(
        functools.partial(_kernel, dmax=dmax, br=block_r),
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (block_r, dmax), lambda p, r: (r, 0),
                memory_space=pltpu.SMEM,
            ),
            pl.BlockSpec((K, block_p), lambda p, r: (0, p)),
        ],
        out_specs=pl.BlockSpec((block_r, block_p), lambda p, r: (r, p)),
        out_shape=jax.ShapeDtypeStruct((R, P), jnp.uint32),
        interpret=interpret,
    )(sel, payload)
