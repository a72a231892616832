"""Public jit'd wrappers for the Pallas kernels, with oracle fallback.

`backend` selection:
  * "pallas"    — pl.pallas_call compiled for the TPU.  With
                  `interpret=True` the kernel body runs through the Pallas
                  interpreter instead, which is how the tests check the
                  kernels off the TPU; nothing turns it on but the caller.
  * "reference" — the pure-jnp oracle from repro.kernels.ref.

The default backend is platform-aware: real Pallas on TPU, reference
elsewhere (the dry-run and CPU smoke tests must produce clean XLA HLO).
"""
from __future__ import annotations

import functools
from typing import Literal

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.flash_decode import flash_decode_pallas
from repro.kernels.lt_encode import lt_encode_pallas
from repro.kernels.spray_select import spray_select_pallas

__all__ = [
    "default_backend",
    "spray_select",
    "lt_encode",
    "flash_attention",
    "flash_decode",
    "lse_combine",
]

Backend = Literal["auto", "pallas", "chunked", "reference"]


def default_backend() -> str:
    # off-TPU, models use the chunked jnp path: same FLOPs as the Pallas
    # kernel, O(S*d) memory, clean XLA HLO for the dry-run roofline
    return "pallas" if jax.default_backend() == "tpu" else "chunked"


def _resolve(backend: Backend) -> str:
    return default_backend() if backend == "auto" else backend


def spray_select(
    counters, c, sa, sb, *, ell: int, method: int, backend: Backend = "auto",
    interpret: bool = False,
):
    if _resolve(backend) == "pallas":
        return spray_select_pallas(
            counters, c, sa, sb, ell=ell, method=method, interpret=interpret
        )
    return jax.jit(
        functools.partial(_ref.spray_select_ref, ell=ell, method=method)
    )(counters, c, sa, sb)


def lt_encode(
    payload, neighbors, valid, *, backend: Backend = "auto",
    interpret: bool = False,
):
    if _resolve(backend) == "pallas":
        return lt_encode_pallas(payload, neighbors, valid, interpret=interpret)
    return jax.jit(_ref.lt_encode_ref)(payload, neighbors, valid)


def flash_attention(
    q, k, v, *, causal=True, window=None, scale=None, q_offset=0,
    backend: Backend = "auto", block_q: int = 512, block_k: int = 512,
    interpret: bool = False,
):
    backend = _resolve(backend)
    if backend == "pallas":
        return flash_attention_pallas(
            q, k, v, causal=causal, window=window, scale=scale,
            q_offset=q_offset, block_q=block_q, block_k=block_k,
            interpret=interpret,
        )
    if backend == "chunked":
        return _ref.flash_attention_chunked(
            q, k, v, causal=causal, window=window, scale=scale,
            q_offset=q_offset, block_k=block_k,
        )
    return _ref.flash_attention_ref(
        q, k, v, causal=causal, window=window, scale=scale, q_offset=q_offset
    )


def flash_decode(
    q, k, v, kv_len, *, scale=None, backend: Backend = "auto",
    block_s: int = 512, return_lse: bool = False, interpret: bool = False,
):
    if _resolve(backend) == "pallas":
        o, m, l = flash_decode_pallas(
            q, k, v, kv_len, scale=scale, block_s=block_s,
            interpret=interpret,
        )
        if return_lse:
            return o, m, l
        denom = jnp.where(l > 0, l, 1.0)
        return (o / denom[..., None]).astype(q.dtype)
    return _ref.flash_decode_ref(
        q, k, v, kv_len, scale=scale, return_lse=return_lse
    )


lse_combine = _ref.lse_combine
