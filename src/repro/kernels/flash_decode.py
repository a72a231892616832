"""Pallas TPU kernel: single-token flash decode over a KV cache shard.

Serving hot loop: one query token per sequence attends over a long KV cache.
Grid (B, KVH, ns) streams the cache in [bs, D] tiles; the `group` query heads
sharing each kv head are processed together as a [group, D] q tile (GQA).
Running (m, l, acc) live in VMEM scratch across the ns axis.

Returns UN-normalized partials (o, m, l) in f32: the caller either normalizes
locally (single shard) or psum-free LSE-combines partials across sequence-
parallel shards (repro.dist.decode_sp) — the distributed-decode pattern that
makes `long_500k` run on a mesh even though no single device holds the cache.

`kv_len` masks the valid prefix per sequence (ragged batches / ring-buffer
caches write garbage past the watermark).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_decode_pallas"]

_NEG_INF = -1e30


def _kernel(
    kvlen_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
    m_scr, l_scr, acc_scr,
    *, scale: float, bs: int, ns: int,
):
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32) * scale          # [group, D]
    k = k_ref[0, :, 0].astype(jnp.float32)               # [bs, D]
    v = v_ref[0, :, 0].astype(jnp.float32)               # [bs, D]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                                    # [group, bs]
    pos = si * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = pos < kvlen_ref[0]
    s = jnp.where(mask, s, _NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.where(mask, jnp.exp(s - m_new[:, None]), 0.0)
    l_new = l_scr[...] * alpha + jnp.sum(p, axis=1)
    acc_new = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_scr[...] = m_new
    l_scr[...] = l_new
    acc_scr[...] = acc_new

    @pl.when(si == ns - 1)
    def _emit():
        o_ref[0, 0] = acc_new
        m_ref[0, 0] = m_new
        l_ref[0, 0] = l_new


@functools.partial(
    jax.jit, static_argnames=("scale", "block_s", "interpret")
)
def flash_decode_pallas(
    q: jax.Array,       # [B, H, D]
    k: jax.Array,       # [B, Sk, KVH, D]
    v: jax.Array,       # [B, Sk, KVH, D]
    kv_len: jax.Array,  # int32[B]
    *,
    scale: float | None = None,
    block_s: int = 512,
    interpret: bool = False,
):
    """Returns (o, m, l): o f32[B, H, D] un-normalized, m/l f32[B, H]."""
    B, H, D = q.shape
    Sk, KVH = k.shape[1], k.shape[2]
    group = H // KVH
    bs = min(block_s, Sk)
    if Sk % bs:
        raise ValueError(f"Sk={Sk} must tile by {bs}")
    ns = Sk // bs
    scale = scale if scale is not None else float(1.0 / np.sqrt(D))
    qg = q.reshape(B, KVH, group, D)
    grid = (B, KVH, ns)
    o, m, l = pl.pallas_call(
        functools.partial(_kernel, scale=scale, bs=bs, ns=ns),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1,), lambda b, h, s: (b,)),
            pl.BlockSpec((1, 1, group, D), lambda b, h, s: (b, h, 0, 0)),
            pl.BlockSpec((1, bs, 1, D), lambda b, h, s: (b, s, h, 0)),
            pl.BlockSpec((1, bs, 1, D), lambda b, h, s: (b, s, h, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, group, D), lambda b, h, s: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, group), lambda b, h, s: (b, h, 0)),
            pl.BlockSpec((1, 1, group), lambda b, h, s: (b, h, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, KVH, group, D), jnp.float32),
            jax.ShapeDtypeStruct((B, KVH, group), jnp.float32),
            jax.ShapeDtypeStruct((B, KVH, group), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((group,), jnp.float32),
            pltpu.VMEM((group,), jnp.float32),
            pltpu.VMEM((group, D), jnp.float32),
        ],
        interpret=interpret,
    )(kv_len, qg, k, v)
    return o.reshape(B, H, D), m.reshape(B, H), l.reshape(B, H)
