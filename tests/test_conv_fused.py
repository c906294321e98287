"""Fused implicit-GEMM conv backend: kernel parity (interpret mode), the
route/fallback layer, and numerical equivalence of the served route with
the XLA reference for every conv/dense node of VGG-16, AlexNet and
MobileNet.

Kernel-level checks run small shapes (reduction length K <= 784, outputs
of order one) against RTOL=1e-4, ATOL=1e-5.  The whole-graph parity
derives each node's bound from float32 accumulation over that node's K
(`test_fused_backend_matches_xla_route_all_nodes`).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cnn import MODELS
from repro.kernels.backend import BACKENDS, KernelBackend, resolve_backend
from repro.kernels.config import default_interpret
from repro.kernels.conv_fused import (
    ACTS,
    apply_act,
    conv2d_fused,
    fused_route_ref,
    matmul_fused,
    supports,
)

RTOL, ATOL = 1e-4, 1e-5  # kernel-level bound on small shapes
F32_U = 2.0 ** -24  # unit roundoff of float32
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


def test_supports_rejects_grouped():
    assert supports(3, 3, 1, groups=1)
    assert not supports(3, 3, 1, groups=2)
    assert not supports(3, 3, 2, groups=16)


# -------------------------------------------------------- backend routing
def test_backend_spec_forms():
    assert resolve_backend("pallas_fused").route == "pallas_fused"
    kb = KernelBackend("pallas_fused")
    assert resolve_backend(kb) is kb
    assert resolve_backend(None).route == "xla"  # the reference
    with pytest.raises(ValueError):
        resolve_backend("notabackend")


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


# ------------------------------------------------- the route dispatcher
# (kind, x shape, w shape, stride, pad): the shapes the benchmark's cells
# serve — VGG-16's 3x3/1, ResNet-50's 7x7/2 stem and 1x1/2 projection,
# ConvNeXt-T's 4x4/4 stem and 7x7 depthwise, and the fc layers
_OPS = {
    "conv3x3s1": ("conv", (2, 16, 16, 8), (3, 3, 8, 16), 1, 1),
    "conv7x7s2": ("conv", (2, 16, 16, 3), (7, 7, 3, 16), 2, 3),
    "conv1x1s2": ("conv", (2, 16, 16, 16), (1, 1, 16, 16), 2, 0),
    "conv4x4s4": ("conv", (2, 16, 16, 3), (4, 4, 3, 16), 4, 0),
    "dw7x7s1": ("depthwise", (2, 16, 16, 16), (7, 7, 1, 16), 1, 3),
    "dense": ("fc", (2, 2, 2, 4), (16, 12), 1, 0),
}


def _run_op(kb, op, x, w, b, act):
    kind, _, _, stride, pad = _OPS[op]
    if kind == "fc":
        return kb.dense(op, x, w, b, act=act)
    if kind == "depthwise":
        return kb.depthwise(op, x, w, b, stride=stride, pad=pad, act=act)
    return kb.conv2d(op, x, w, b, stride=stride, pad=pad, act=act)


@pytest.mark.parametrize("interpret", [True, False], ids=["kernel", "xla-fused"])
@pytest.mark.parametrize("act", ACTS)
@pytest.mark.parametrize("op", list(_OPS))
def test_backend_route_matches_reference(op, act, interpret):
    """The served route — the Pallas kernel in interpret mode, or the fused
    XLA lowering it resolves to off-TPU — fuses ``act`` into its epilogue,
    takes no fallback, and equals the reference route with the activation
    applied after."""
    _, xs, ws, _, _ = _OPS[op]
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.standard_normal(xs), jnp.float32)
    w = jnp.asarray(rng.standard_normal(ws) / np.sqrt(np.prod(ws[:-1])), jnp.float32)
    b = jnp.asarray(rng.standard_normal(ws[-1:]) * 0.1, jnp.float32)
    kb = KernelBackend("pallas_fused", interpret=interpret)
    got, act_done = _run_op(kb, op, x, w, b, act)
    ref, ref_done = _run_op(KernelBackend("xla"), op, x, w, b, act)
    assert act_done and not ref_done and kb.fallbacks == {}
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(apply_act(ref, act)), rtol=RTOL, atol=ATOL
    )


# --------------------------------------------------- per-node graph parity
def _full_env(graph, params, x, backend):
    """Execute every node, keeping ALL intermediate tensors (no pruning)."""
    kb = resolve_backend(backend)
    env = {"input": x}
    for n in graph.nodes:
        env[n.name] = graph._apply_node(n, params, env, backend=kb)
    return env


def _accumulation_bound(k, s):
    """2 * gamma_{k+1} * s / (1 - gamma_{k+1}): see
    test_fused_backend_matches_xla_route_all_nodes."""
    gamma = (k + 1) * F32_U / (1 - (k + 1) * F32_U)
    return 2 * gamma * s / (1 - gamma)


@pytest.mark.parametrize("name", ["vgg16", "alexnet", "mobilenet"])
def test_fused_backend_matches_xla_route_all_nodes(name):
    """The served route equals the XLA reference at every major node, at
    the node's real shape, each node given the served route's own input.

    The bound: a node computes ``y = sum_{i<=K} x_i w_i + b`` with
    ``K = prod(w.shape[:-1])`` (fh*fw*cin/groups for a conv, fh*fw for a
    depthwise conv, the fan-in for fc).  In float32, in whatever order a
    route sums, its ``y`` is within ``gamma_{K+1} * S`` of the exact value,
    where ``S = sum |x_i w_i| + |b|``, ``gamma_n = n*u / (1 - n*u)`` and
    ``u = 2**-24`` (K rounded products, K additions; Higham, *Accuracy and
    Stability of Numerical Algorithms*, 2nd ed., §3.1).  Two routes thus
    differ by at most ``2 * gamma_{K+1} * S`` per element, and ReLU, the
    only activation of these nets, is 1-Lipschitz.  ``S`` is the reference
    route run on ``|x|, |w|, |b|``; in float32 it reads low by at most a
    factor ``1 - gamma_{K+1}``, which the bound divides out."""
    g = MODELS[name]()
    params = g.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(5)  # Graph.init's biases are zero: a dropped
    for p in params.values():  # bias would pass unseen
        p["b"] = jnp.asarray(rng.standard_normal(p["b"].shape) * 0.1, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, *g.input_shape), jnp.float32)
    env = _full_env(g, params, x, "pallas_fused")
    xla = KernelBackend("xla")
    checked = 0
    for n in g.major_nodes():
        assert n.attrs.get("act", "none") in ("none", "relu"), n.name
        p = params[n.name]
        got = np.asarray(env[n.name])
        want = np.asarray(g._apply_node(n, params, env, xla))
        s = g._apply_node(
            n, {n.name: jax.tree.map(jnp.abs, p)},
            {i: jnp.abs(env[i]) for i in n.inputs}, xla,
        )
        bound = _accumulation_bound(int(np.prod(p["w"].shape[:-1])), np.asarray(s))
        assert got.shape == want.shape
        over = np.abs(got - want) > bound
        assert not over.any(), (
            f"{name}:{n.name}: {over.sum()} of {over.size} elements beyond "
            f"the bound, worst {np.max(np.abs(got - want) / bound):.3g}x it"
        )
        checked += 1
    assert checked == len(g.major_nodes())


def test_backend_names_stable():
    assert BACKENDS == ("xla", "pallas_fused")
