"""The depthwise Pallas kernel (interpret mode) against XLA's grouped conv
at HIGHEST precision, and the shapes it takes (`KernelBackend.depthwise`
routes by them: tests/test_conv_fused.py).

Widths are the published ones (ConvNeXt-T's 96-768 at k=7, MobileNet's
32-1024 at k=3); spatial sizes are cut to keep the interpreter quick.
"""
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from repro.kernels import dwconv
from repro.kernels.conv_fused import ACTS, apply_act
from repro.kernels.dwconv import dwconv2d_fused

RTOL, ATOL = 1e-4, 1e-5  # the fused-backend parity bound of test_conv_fused.py


@pytest.fixture(autouse=True)
def _hermetic_interpret_env(monkeypatch):
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)


def _case(hw, c, k, seed=0, batch=2):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((batch, hw, hw, c)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((k, k, 1, c)) / k, jnp.float32)
    b = jnp.asarray(rng.standard_normal((c,)), jnp.float32)
    return x, w, b


def _oracle(x, w, b, stride, pad, act):
    y = lax.conv_general_dilated(
        x, w, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=x.shape[-1], precision=lax.Precision.HIGHEST,
    )
    return apply_act(y if b is None else y + b, act)


@pytest.mark.parametrize("act", ACTS)
@pytest.mark.parametrize(
    "hw,c,k",
    [
        (28, 96, 7), (14, 192, 7), (7, 384, 7), (7, 768, 7),  # ConvNeXt-T s1-s4
        (14, 32, 3), (7, 256, 3), (7, 1024, 3),  # MobileNet dw1, dw6, dw13
    ],
    ids=["s1", "s2", "s3", "s4", "mobilenet-dw1", "mobilenet-dw6", "mobilenet-dw13"],
)
def test_dwconv_matches_grouped_conv(hw, c, k, act):
    x, w, b = _case(hw, c, k, batch=1)
    got = dwconv2d_fused(x, w, b, pad=k // 2, act=act, interpret=True)
    np.testing.assert_allclose(
        got, _oracle(x, w, b, 1, k // 2, act), rtol=RTOL, atol=ATOL
    )


@pytest.mark.parametrize("hw", [9, 5, 1], ids=["odd", "strip-of-one", "one-pixel"])
def test_dwconv_without_bias_at_odd_sizes(hw):
    """No bias, and heights whose strips are one row (9, 5 prime to the
    strip sizes) down to a single pixel, where every tap but the centre
    reads the zero halo."""
    x, w, _ = _case(hw, 8, 7, seed=1)
    got = dwconv2d_fused(x, w, None, pad=3, interpret=True)
    np.testing.assert_allclose(
        got, _oracle(x, w, None, 1, 3, "none"), rtol=RTOL, atol=ATOL
    )


@pytest.mark.parametrize(
    "k,stride,pad,ok",
    [(7, 1, 3, True), (3, 1, 1, True), (3, 2, 1, False), (3, 1, 0, False),
     (4, 1, 2, False)],
    ids=["k7", "k3", "stride2", "valid", "even-k"],
)
def test_supports_takes_stride_one_same_odd(k, stride, pad, ok):
    assert dwconv.supports(14, 14, 64, k, stride, pad) is ok


def test_supports_declines_a_block_beyond_vmem():
    assert dwconv.supports(112, 112, 32, 3, 1, 1)  # MobileNet dw1 at 224
    assert not dwconv.supports(1024, 1024, 64, 3, 1, 1)


def test_dwconv_rejects_what_it_does_not_support():
    x, w, b = _case(8, 8, 3)
    with pytest.raises(ValueError, match="stride 1"):
        dwconv2d_fused(x, w, b, stride=2, pad=1, interpret=True)
