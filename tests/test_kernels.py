"""Pallas kernel sweeps: shapes x dtypes vs the pure-jnp oracles.

All kernels run in interpret mode on CPU (`interpret=True`, the body
executes in Python);
integer kernels must match EXACTLY, float kernels to f32 accumulation tol.
"""
import numpy as np
import jax.numpy as jnp
import pytest
pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core.profile import quantize_profile
from repro.kernels import ops, ref

RNG = np.random.default_rng(42)


# ---------------------------------------------------------------------------
# spray_select
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("method", [0, 1, 2])
@pytest.mark.parametrize("ell,n", [(10, 5), (8, 3), (12, 64), (10, 128)])
def test_spray_select_sweep(method, ell, n):
    prof = quantize_profile(RNG.random(n) + 0.01, ell)
    counters = jnp.asarray(
        RNG.integers(0, 2**31, 2048, dtype=np.uint32)
    )
    got = ops.spray_select(
        counters, prof.c, 7 % (1 << ell), 9, ell=ell, method=method,
        backend="pallas", interpret=True,
    )
    want = ref.spray_select_ref(
        counters, prof.c, 7 % (1 << ell), 9, ell=ell, method=method
    )
    assert np.array_equal(np.asarray(got), np.asarray(want))


@given(
    st.integers(4, 12),
    st.integers(2, 32),
    st.integers(0, 2**16),
)
@settings(max_examples=10, deadline=None)
def test_spray_select_property(ell, n, sa):
    prof = quantize_profile(np.arange(1, n + 1, dtype=float), ell)
    counters = jnp.arange(1024, dtype=jnp.uint32)
    got = ops.spray_select(
        counters, prof.c, sa % (1 << ell), 3, ell=ell, method=1,
        backend="pallas", interpret=True,
    )
    want = ref.spray_select_ref(
        counters, prof.c, sa % (1 << ell), 3, ell=ell, method=1
    )
    assert np.array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# lt_encode
# ---------------------------------------------------------------------------
@pytest.mark.parametrize(
    "K,P,R,dmax", [(64, 512, 16, 8), (128, 1024, 32, 16), (16, 512, 8, 4)]
)
def test_lt_encode_sweep(K, P, R, dmax):
    payload = jnp.asarray(RNG.integers(0, 2**32, (K, P), dtype=np.uint32))
    neigh = jnp.asarray(RNG.integers(0, K, (R, dmax), dtype=np.int32))
    valid = jnp.asarray(RNG.random((R, dmax)) < 0.7)
    got = ops.lt_encode(payload, neigh, valid, backend="pallas", interpret=True)
    want = ref.lt_encode_ref(payload, neigh, valid)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_lt_encode_degree_one_is_copy():
    payload = jnp.asarray(RNG.integers(0, 2**32, (8, 512), dtype=np.uint32))
    neigh = jnp.asarray(np.arange(8, dtype=np.int32)[:, None])
    valid = jnp.ones((8, 1), bool)
    got = ops.lt_encode(payload, neigh, valid, backend="pallas", interpret=True)
    assert np.array_equal(np.asarray(got), np.asarray(payload))


# ---------------------------------------------------------------------------
# flash attention (train/prefill)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,H,KVH,S,D,causal,window",
    [
        (2, 4, 2, 256, 64, True, None),
        (1, 8, 8, 128, 128, False, None),
        (2, 4, 1, 256, 64, True, 64),
        (1, 2, 2, 512, 32, True, 128),
    ],
)
def test_flash_attention_sweep(B, H, KVH, S, D, causal, window, dtype):
    q = jnp.asarray(RNG.standard_normal((B, H, S, D)), dtype)
    k = jnp.asarray(RNG.standard_normal((B, KVH, S, D)), dtype)
    v = jnp.asarray(RNG.standard_normal((B, KVH, S, D)), dtype)
    want = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    got = ops.flash_attention(
        q, k, v, causal=causal, window=window, backend="pallas", interpret=True,
        block_q=128, block_k=128,
    )
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=tol, rtol=tol,
    )
    # the chunked jnp path (model default off-TPU) must agree too
    got_c = ops.flash_attention(
        q, k, v, causal=causal, window=window, backend="chunked", block_k=128
    )
    np.testing.assert_allclose(
        np.asarray(got_c, np.float32), np.asarray(want, np.float32),
        atol=tol, rtol=tol,
    )


def test_flash_attention_q_offset():
    """Chunked prefill continuation: q_offset shifts causal masking."""
    B, H, S, D = 1, 2, 128, 32
    q = jnp.asarray(RNG.standard_normal((B, H, 64, D)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((B, H, S, D)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((B, H, S, D)), jnp.float32)
    got = ops.flash_attention(
        q, k, v, causal=True, q_offset=64, backend="pallas", interpret=True,
        block_q=64, block_k=64,
    )
    want = ref.flash_attention_ref(q, k, v, causal=True, q_offset=64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


# ---------------------------------------------------------------------------
# flash decode + LSE combine
# ---------------------------------------------------------------------------
@pytest.mark.parametrize(
    "B,H,KVH,S,D", [(3, 8, 2, 1024, 64), (2, 4, 4, 512, 128), (1, 16, 2, 2048, 64)]
)
def test_flash_decode_sweep(B, H, KVH, S, D):
    q = jnp.asarray(RNG.standard_normal((B, H, D)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((B, S, KVH, D)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((B, S, KVH, D)), jnp.float32)
    kv_len = jnp.asarray(RNG.integers(1, S, B), jnp.int32)
    got = ops.flash_decode(
        q, k, v, kv_len, backend="pallas", block_s=256, interpret=True
    )
    want = ref.flash_decode_ref(q, k, v, kv_len)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5
    )


def test_lse_combine_equals_full():
    B, H, KVH, S, D = 2, 8, 2, 1024, 64
    q = jnp.asarray(RNG.standard_normal((B, H, D)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((B, S, KVH, D)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((B, S, KVH, D)), jnp.float32)
    kv_len = jnp.asarray([900, 333], jnp.int32)
    want = ref.flash_decode_ref(q, k, v, kv_len)
    shards = 8
    per = S // shards
    parts = []
    for s in range(shards):
        lens = jnp.clip(kv_len - s * per, 0, per)
        parts.append(
            ops.flash_decode(
                q, k[:, s * per : (s + 1) * per], v[:, s * per : (s + 1) * per],
                lens, backend="pallas", block_s=128, return_lse=True,
                interpret=True,
            )
        )
    got = ops.lse_combine(parts)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5
    )
