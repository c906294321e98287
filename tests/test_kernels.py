"""Pallas kernels vs pure-jnp oracles — shape/dtype sweeps in interpret mode."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ref
from repro.kernels.flash_decode import flash_decode

RNG = np.random.default_rng(42)


def _arr(shape, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(RNG.standard_normal(shape) * scale, dtype)


# ------------------------------------------------------------ flash decode
@pytest.mark.parametrize(
    "hq,d,s,length,bs",
    [(8, 64, 256, 256, 128), (4, 32, 300, 177, 64), (16, 128, 128, 1, 128), (1, 64, 512, 400, 128)],
)
def test_flash_decode_matches_ref(hq, d, s, length, bs):
    q = _arr((hq, d), scale=0.5)
    k = _arr((s, d), scale=0.5)
    v = _arr((s, d))
    got = flash_decode(q, k, v, jnp.int32(length), block_s=bs, interpret=True)
    want = ref.flash_decode_ref(q, k, v, length)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_decode_dtypes(dtype):
    q, k, v = _arr((8, 64), dtype), _arr((256, 64), dtype), _arr((256, 64), dtype)
    got = flash_decode(q, k, v, jnp.int32(200), interpret=True)
    want = ref.flash_decode_ref(q, k, v, 200)
    tol = 2e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        got.astype(jnp.float32), want.astype(jnp.float32), rtol=tol, atol=tol
    )


@given(st.integers(min_value=1, max_value=300))
@settings(max_examples=10, deadline=None)
def test_flash_decode_length_property(length):
    """Only the first ``length`` cache slots may influence the output."""
    q, k, v = _arr((4, 32), scale=0.5), _arr((300, 32), scale=0.5), _arr((300, 32))
    got = flash_decode(q, k, v, jnp.int32(length), block_s=64, interpret=True)
    # corrupt the cache beyond `length`: output must not change
    k2 = k.at[length:].set(99.0)
    v2 = v.at[length:].set(-99.0)
    got2 = flash_decode(q, k2, v2, jnp.int32(length), block_s=64, interpret=True)
    np.testing.assert_allclose(got, got2, rtol=1e-6, atol=1e-6)


def test_flash_decode_block_invariance():
    q, k, v = _arr((8, 64), scale=0.5), _arr((384, 64), scale=0.5), _arr((384, 64))
    o1 = flash_decode(q, k, v, jnp.int32(333), block_s=64, interpret=True)
    o2 = flash_decode(q, k, v, jnp.int32(333), block_s=128, interpret=True)
    np.testing.assert_allclose(o1, o2, rtol=1e-5, atol=1e-5)
