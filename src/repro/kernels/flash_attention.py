"""Pallas TPU kernel: causal / sliding-window GQA flash attention.

Online-softmax attention for training and prefill: never materializes the
[Sq, Sk] logit matrix.  Grid (B, H, nq, nk) executes the nk axis innermost
and sequentially on TPU, so the running (m, l, acc) state for one q tile
lives in VMEM scratch across nk steps; the normalized output tile is emitted
on the last nk step.

Tiling: q tile [bq, D] and kv tiles [bk, D] sized so q + k + v + acc fit
VMEM (default 512x128x4 tiles ~ 0.8 MB); D is the head dim (MXU-aligned at
128 for all assigned archs except h2o-danube's 120, which the compiler pads).
GQA is free: the kv BlockSpec index-maps head h -> h // group, so kv tiles
are fetched once per q-head group member without host-side repetition.

Causal and sliding-window masks are applied per-tile from absolute positions;
`q_offset` supports chunked prefill continuation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention_pallas"]

_NEG_INF = -1e30


def _kernel(
    q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
    *, scale: float, causal: bool, window: int | None,
    bq: int, bk: int, nk: int, q_offset: int,
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32) * scale          # [bq, D]
    k = k_ref[0, 0].astype(jnp.float32)                  # [bk, D]
    v = v_ref[0, 0].astype(jnp.float32)                  # [bk, D]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                                    # [bq, bk]

    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + q_offset
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), dtype=bool)
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask, s, _NEG_INF)

    m_prev = m_scr[...]                                  # [bq]
    m_cur = jnp.max(s, axis=1)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    p = jnp.where(mask, p, 0.0)
    l_new = l_scr[...] * alpha + jnp.sum(p, axis=1)
    acc_new = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_scr[...] = m_new
    l_scr[...] = l_new
    acc_scr[...] = acc_new

    @pl.when(ki == nk - 1)
    def _emit():
        denom = jnp.where(l_new > 0, l_new, 1.0)
        o_ref[0, 0] = (acc_new / denom[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "window", "scale", "q_offset", "block_q", "block_k",
        "interpret",
    ),
)
def flash_attention_pallas(
    q: jax.Array,  # [B, H, Sq, D]
    k: jax.Array,  # [B, KVH, Sk, D]
    v: jax.Array,  # [B, KVH, Sk, D]
    *,
    causal: bool = True,
    window: int | None = None,
    scale: float | None = None,
    q_offset: int = 0,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    B, H, Sq, D = q.shape
    KVH, Sk = k.shape[1], k.shape[2]
    group = H // KVH
    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    if Sq % bq or Sk % bk:
        raise ValueError(f"Sq={Sq}/Sk={Sk} must tile by ({bq},{bk})")
    scale = scale if scale is not None else float(1.0 / np.sqrt(D))
    nq, nk = Sq // bq, Sk // bk
    grid = (B, H, nq, nk)
    return pl.pallas_call(
        functools.partial(
            _kernel, scale=scale, causal=causal, window=window,
            bq=bq, bk=bk, nk=nk, q_offset=q_offset,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec(
                (1, 1, bk, D), lambda b, h, i, j: (b, h // group, j, 0)
            ),
            pl.BlockSpec(
                (1, 1, bk, D), lambda b, h, i, j: (b, h // group, j, 0)
            ),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
