"""Fused implicit-GEMM conv backend: kernel parity (interpret mode), the
backend routing/fallback layer, and the ISSUE-3 acceptance criterion —
numerical equivalence of the fused backend with the XLA route for every
conv/dense node of VGG-16, AlexNet and MobileNet, quantized path included.

Pinned tolerances (acceptance): RTOL=1e-4, ATOL=1e-5 for graph routes;
kernel-level interpret checks use the same bound.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cnn import MODELS
from repro.cnn.layers import im2col
from repro.cnn.quant import qgemm, quantize_graph_params
from repro.kernels.backend import BACKENDS, KernelBackend, resolve_backend
from repro.kernels.config import default_interpret
from repro.kernels.conv_fused import (
    conv2d_fused,
    fused_route_ref,
    matmul_fused,
    qconv2d_fused,
    qfused_route_ref,
    supports,
)

RTOL, ATOL = 1e-4, 1e-5  # pinned acceptance tolerances
RNG = np.random.default_rng(7)


@pytest.fixture(autouse=True)
def _hermetic_interpret_env(monkeypatch):
    """A user-set REPRO_PALLAS_INTERPRET must not flip full-graph routes
    into interpret mode mid-suite; tests opt in via explicit arguments."""
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)


def _arr(shape, scale=1.0):
    return jnp.asarray(RNG.standard_normal(shape) * scale, jnp.float32)


def _conv_oracle(x, w, b, stride, pad, groups=1, relu=False):
    y = jax.lax.conv_general_dilated(
        x, w, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups,
    )
    if b is not None:
        y = y + b
    return jnp.maximum(y, 0.0) if relu else y


# ------------------------------------------------------ kernel (interpret)
@pytest.mark.parametrize(
    "hw,c,k,cout,stride,pad,bm,bn,bk",
    [
        (8, 3, 3, 5, 1, 1, 4, 4, 2),     # non-divisible tiles everywhere
        (12, 4, 5, 8, 2, 2, 6, 8, 4),
        (7, 8, 1, 16, 1, 0, 7, 16, 8),   # 1x1 conv
        (14, 2, 7, 6, 2, 3, 3, 8, 2),
        (9, 5, 3, 7, 3, 1, 128, 128, 128),  # blocks larger than dims
    ],
)
def test_conv_fused_kernel_matches_oracle(hw, c, k, cout, stride, pad, bm, bn, bk):
    x = _arr((2, hw, hw, c))
    w = _arr((k, k, c, cout))
    b = _arr((cout,))
    got = conv2d_fused(
        x, w, b, stride=stride, pad=pad, act="relu",
        block_m=bm, block_n=bn, block_k=bk, interpret=True,
    )
    want = _conv_oracle(x, w, b, stride, pad, relu=True)
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=RTOL, atol=ATOL)


def test_conv_fused_blocking_invariance():
    x, w, b = _arr((1, 10, 10, 6)), _arr((3, 3, 6, 8)), _arr((8,))
    o1 = conv2d_fused(x, w, b, pad=1, block_m=2, block_n=4, block_k=3, interpret=True)
    o2 = conv2d_fused(x, w, b, pad=1, block_m=10, block_n=8, block_k=6, interpret=True)
    np.testing.assert_allclose(o1, o2, rtol=1e-5, atol=1e-6)


def test_matmul_fused_matches_oracle():
    a, w, b = _arr((5, 70)), _arr((70, 33)), _arr((33,))
    got = matmul_fused(a, w, b, block_m=4, block_n=16, block_k=32, act="relu", interpret=True)
    want = jnp.maximum(a @ w + b, 0.0)
    np.testing.assert_allclose(got, want, rtol=RTOL, atol=ATOL)


def test_qconv_fused_matches_qgemm_route():
    """Quantized kernel == the im2col + qgemm patch-matrix route."""
    x = _arr((2, 8, 8, 4))
    w = _arr((3, 3, 4, 6))
    b = _arr((6,))
    qp = quantize_graph_params({"l": {"w": w, "b": b}})["l"]
    got = qconv2d_fused(
        x, qp["qw"], qp["scale"], qp["zp"], b, (3, 3, 4, 6),
        stride=1, pad=1, interpret=True,
    )
    cols = im2col(x, 3, 3, 1, 1)
    want = qgemm(
        cols.reshape(-1, cols.shape[-1]), qp["qw"], qp["scale"], qp["zp"]
    ).reshape(2, 8, 8, 6) + b
    np.testing.assert_allclose(got, want, rtol=RTOL, atol=ATOL)


def test_make_quant_conv_fn_routes_match():
    """The quant.py closure runs the same fused quant op on both routes."""
    from repro.cnn.quant import make_quant_conv_fn

    x = _arr((1, 8, 8, 4))
    w = _arr((3, 3, 4, 6))
    b = _arr((6,))
    qp = quantize_graph_params({"l": {"w": w, "b": b}})["l"]
    xla_fn = make_quant_conv_fn(qp, stride=1, pad=1, act="relu")
    np.testing.assert_allclose(
        xla_fn(x),
        qconv2d_fused(
            x, qp["qw"], qp["scale"], qp["zp"], b, (3, 3, 4, 6),
            stride=1, pad=1, act="relu", interpret=True,
        ),
        rtol=RTOL, atol=ATOL,
    )


def test_supports_rejects_grouped():
    assert supports(3, 3, 1, groups=1)
    assert not supports(3, 3, 1, groups=2)
    assert not supports(3, 3, 2, groups=16)


# -------------------------------------------------------- backend routing
def test_backend_spec_forms():
    kb = resolve_backend({"conv1": "pallas_fused"})
    assert kb.for_node("conv1") == "pallas_fused"
    assert kb.for_node("anything_else") == "xla"  # default
    kb = resolve_backend(lambda name: "pallas" if name.startswith("fc") else "xla")
    assert kb.for_node("fc6") == "pallas"
    assert kb.for_node("conv2") == "xla"
    assert resolve_backend(None) is None
    kb = KernelBackend(spec="pallas_fused")
    assert resolve_backend(kb) is kb
    with pytest.raises(ValueError):
        resolve_backend("notabackend")
    with pytest.raises(ValueError):
        resolve_backend({"a": "nope"}).for_node("a")


@pytest.mark.parametrize("groups,stride,pad", [(2, 1, 1), (4, 2, 1), (2, 2, 2)])
def test_backend_grouped_conv_fallback_parity(groups, stride, pad):
    """Grouped convs route through the automatic XLA fallback (recorded in
    ``fallbacks``) and stay numerically equivalent to the native conv."""
    cin, cout = 8, 12
    x = _arr((2, 10, 10, cin))
    w = _arr((3, 3, cin // groups, cout))
    b = _arr((cout,))
    kb = resolve_backend("pallas_fused")
    y, act_done = kb.conv2d(
        "g", x, w, b, stride=stride, pad=pad, groups=groups, act="relu"
    )
    assert act_done  # the fallback still fuses the epilogue
    assert "g" in kb.fallbacks and "groups" in kb.fallbacks["g"]
    want = _conv_oracle(x, w, b, stride, pad, groups=groups, relu=True)
    np.testing.assert_allclose(y, want, rtol=RTOL, atol=ATOL)


@pytest.mark.parametrize("interpret", [None, True], ids=["xla-route", "kernel"])
@pytest.mark.parametrize("stride,pad", [(1, 1), (2, 1)])
def test_backend_depthwise_fallback_parity(stride, pad, interpret):
    """Stride 1 is the depthwise kernel's shape: it runs the kernel
    (interpret mode) or, off-TPU by default, XLA's fused route, and is no
    fallback either way; stride 2 keeps XLA's grouped conv and is
    recorded."""
    c = 6
    x = _arr((2, 9, 9, c))
    w = _arr((3, 3, 1, c))
    b = _arr((c,))
    kb = resolve_backend("pallas_fused", interpret=interpret)
    y, act_done = kb.depthwise("dw", x, w, b, stride=stride, pad=pad, act="relu")
    assert act_done and kb.fallbacks == ({} if stride == 1 else {"dw": "depthwise"})
    want = _conv_oracle(x, w, b, stride, pad, groups=c, relu=True)
    np.testing.assert_allclose(y, want, rtol=RTOL, atol=ATOL)


def test_interpret_default_follows_platform(monkeypatch):
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    # this suite runs on CPU: off-TPU the default must be interpret
    assert jax.default_backend() != "tpu"
    assert default_interpret(None) is True
    assert default_interpret(False) is False  # explicit wins
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    assert default_interpret(None) is False
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    assert default_interpret(None) is True


@pytest.mark.parametrize("value", ["1", "true"])
def test_interpret_override_raises_on_tpu(monkeypatch, value):
    """On a TPU an env value asking for the interpreter raises, in the
    kernel entry points and on the serving backend, instead of running
    the kernels interpreted."""
    import repro.kernels.backend as backend_mod
    import repro.kernels.config as config_mod

    monkeypatch.setattr(config_mod, "on_tpu", lambda: True)
    monkeypatch.setattr(backend_mod, "on_tpu", lambda: True)
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", value)
    with pytest.raises(RuntimeError, match="REPRO_PALLAS_INTERPRET"):
        default_interpret(None)
    x, w, b = _arr((1, 6, 6, 2)), _arr((3, 3, 2, 4)), _arr((4,))
    with pytest.raises(RuntimeError, match="REPRO_PALLAS_INTERPRET"):
        resolve_backend("pallas_fused").conv2d("c", x, w, b, pad=1, act="relu")
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    assert default_interpret(None) is False  # compiled, as on any TPU run


# ------------------------------------- acceptance: per-node graph parity
def _full_env(graph, params, x, backend):
    """Execute every node, keeping ALL intermediate tensors (no pruning)."""
    kb = resolve_backend(backend)
    env = {"input": x}
    for n in graph.nodes:
        env[n.name] = graph._apply_node(n, params, env, backend=kb)
    return env


@pytest.mark.parametrize("name", ["vgg16", "alexnet", "mobilenet"])
def test_fused_backend_matches_xla_route_all_nodes(name):
    """ISSUE 3 acceptance: the fused backend is numerically equivalent to
    the XLA route for ALL conv/dense nodes (checked at every major node's
    real shape, not just the logits)."""
    g = MODELS[name]()
    params = g.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, *g.input_shape), jnp.float32)
    env_xla = _full_env(g, params, x, "xla")
    env_fused = _full_env(g, params, x, "pallas_fused")
    checked = 0
    for n in g.major_nodes():
        a, b = env_xla[n.name], env_fused[n.name]
        assert a.shape == b.shape
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=RTOL, atol=ATOL,
            err_msg=f"{name}:{n.name}",
        )
        checked += 1
    assert checked == len(g.major_nodes())


@pytest.mark.parametrize(
    "name",
    [
        # vgg16's 13 full-size quantized convs take ~25s alone; tier-1 keeps
        # the two small models, the slow suite (and the CI kernels step with
        # -m slow) still covers vgg16
        pytest.param("vgg16", marks=pytest.mark.slow),
        "alexnet",
        "mobilenet",
    ],
)
def test_quantized_fused_route_matches_qgemm_all_conv_nodes(name):
    """Quantized acceptance: for every groups==1 conv descriptor of the
    graph, the fused quant route (int32 direct conv + merged-scale
    epilogue) matches the patch-matrix im2col+qgemm route."""
    g = MODELS[name]()
    rng = np.random.default_rng(3)
    seen = set()
    for d in g.descriptors():
        if d.kind != "conv" or d.groups != 1:
            continue
        geo = (d.i_h, d.i_w, d.i_d, d.f_h, d.stride, d.pad, d.ofm)
        if geo in seen:  # identical geometry -> identical computation
            continue
        seen.add(geo)
        # cap spatial dims: the quant math is per-element, equivalence at
        # 28x28 is equivalence at 224x224 (same descriptors otherwise)
        h = min(d.i_h, 28)
        wd = min(d.i_w, 28)
        x = jnp.asarray(rng.standard_normal((1, h, wd, d.i_d)), jnp.float32)
        w = jnp.asarray(
            rng.standard_normal((d.f_h, d.f_w, d.i_d, d.ofm)) * 0.1, jnp.float32
        )
        b = jnp.asarray(rng.standard_normal((d.ofm,)), jnp.float32)
        qp = quantize_graph_params({"l": {"w": w, "b": b}})["l"]
        got = qfused_route_ref(
            x, qp["qw"], qp["scale"], qp["zp"], b, w.shape,
            stride=d.stride, pad=d.pad,
        )
        cols = im2col(x, d.f_h, d.f_w, d.stride, d.pad)
        want = qgemm(
            cols.reshape(-1, cols.shape[-1]), qp["qw"], qp["scale"], qp["zp"]
        ).reshape(got.shape) + b
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=RTOL, atol=ATOL,
            err_msg=f"{name}:{d.name}",
        )
    assert seen  # every net exercised at least one conv geometry


def test_backend_names_stable():
    assert BACKENDS == ("xla", "pallas", "pallas_fused")
