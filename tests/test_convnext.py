"""ConvNeXt-T on the CPU: LayerNorm and exact GELU, the graph's structure,
stage splitting across the residual stream, and the fused kernel's GELU
epilogue against the XLA route at every major node (64x64 images, every
width as published)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from repro.cnn import MODELS, convnext
from repro.cnn.layers import layer_norm
from repro.kernels.backend import KernelBackend
from repro.kernels.conv_fused import apply_act, conv2d_fused, erf_f32, fused_route_ref

HW = 64
RTOL, ATOL = 1e-4, 1e-5  # the kernel-level bound of test_conv_fused.py
F32_ULP = float(np.finfo(np.float32).eps)  # one ulp of 1.0


@pytest.fixture(scope="module")
def tiny():
    """(graph at 64x64, params, images, every node's output on the xla route)."""
    g = MODELS["convnext_tiny"]()
    g.input_shape = (HW, HW, 3)
    # Graph.init's shapes and He scales, drawn by numpy: threefry takes
    # ~7 s of CPU for 28.6 M weights
    rng = np.random.default_rng(0)
    params = jax.tree.map(
        lambda s: jnp.asarray(
            rng.standard_normal(s.shape, np.float32)
            * np.sqrt((2.0 if len(s.shape) == 4 else 1.0) / np.prod(s.shape[:-1]))
            if len(s.shape) > 1 else np.zeros(s.shape, np.float32)
        ),
        jax.eval_shape(g.init, jax.random.PRNGKey(0)),
    )
    x = jax.random.normal(jax.random.PRNGKey(1), (2, HW, HW, 3), jnp.float32)

    def every_node(p, x):
        env = {"input": x}
        for n in g.nodes:
            env[n.name] = g._apply_node(n, p, env, backend=KernelBackend("xla"))
        return env

    return g, params, x, jax.jit(every_node)(params, x)


# ------------------------------------------------------------ the layers
@pytest.mark.parametrize("shape", [(2, 5, 7, 96), (3, 768), (768,)])
def test_layer_norm_against_formula(shape):
    x = np.random.default_rng(0).standard_normal(shape).astype(np.float32) * 3 + 1
    got = np.asarray(layer_norm(jnp.asarray(x), 1e-6))
    x64 = x.astype(np.float64)
    mu = x64.mean(-1, keepdims=True)
    want = (x64 - mu) / np.sqrt(((x64 - mu) ** 2).mean(-1, keepdims=True) + 1e-6)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    assert got.shape == shape


def test_gelu_is_exact():
    x = jnp.linspace(-8.0, 8.0, 20001, dtype=jnp.float32)
    gelu = apply_act(x, "gelu")
    np.testing.assert_array_equal(gelu, jax.nn.gelu(x, approximate=False))
    # the tanh approximation is another function
    assert float(jnp.abs(gelu - jax.nn.gelu(x, approximate=True)).max()) > 1e-4
    with pytest.raises(ValueError):
        apply_act(x, "silu")


def test_kernel_erf_within_float32_rounding():
    """The epilogue's erf (Mosaic cannot lower lax.erf) stays within 5
    float32 ulps of 1.0 of lax.erf, and GELU built on it within 3 ulps of
    max(|x|, 1) of the exact GELU, over [-10, 10]: what 0.5 * x * (1 +
    erf) gives for an erf that is off by its last bits."""
    x = jnp.linspace(-10.0, 10.0, 400001, dtype=jnp.float32)
    assert float(jnp.abs(erf_f32(x) - lax.erf(x)).max()) <= 5 * F32_ULP
    got = np.asarray(apply_act(x, "gelu", in_kernel=True), np.float64)
    want = np.asarray(jax.nn.gelu(x, approximate=False), np.float64)
    scale = np.maximum(np.abs(np.asarray(x, np.float64)), 1.0)
    assert (np.abs(got - want) / scale).max() <= 3 * F32_ULP


# ----------------------------------------------------------- the graph
def test_structure_and_names(tiny):
    g = tiny[0]
    majors = g.major_nodes()
    assert len(majors) == 59
    kinds = [n.kind for n in majors]
    assert kinds.count("depthwise") == 18 and kinds.count("fc") == 1
    names = [n.name for n in majors]
    assert names[:5] == ["stem", "s1b1_dw", "s1b1_pw1", "s1b1_pw2", "s1b2_dw"]
    assert {"ds2", "ds3", "ds4", "s3b9_pw2", "fc"} <= set(names)
    acts = {n.name: n.attrs.get("act") for n in g.nodes}
    assert acts["s2b1_pw1"] == "gelu" and acts["s2b1_pw2"] == "none"
    assert acts["s2b1_add"] == "none" and acts["stem"] == "none"
    assert sum(n.kind == "layernorm" for n in g.nodes) == 1 + 3 + 18 + 1
    shapes = g.infer_shapes()
    assert shapes["stem"] == (HW // 4, HW // 4, 96)
    assert shapes["s4b3_add"] == (HW // 32, HW // 32, 768)
    assert shapes["head_ln"] == (768,)


def test_apply_range_over_pipeit_allocation_equals_apply(tiny):
    """Cuts inside blocks: the residual stream crosses beside the branch."""
    g, params, x, env = tiny
    whole = env[g.nodes[-1].name]
    alloc = [tuple(range(0, 5)), tuple(range(5, 23)), tuple(range(23, 41)),
             tuple(range(41, 59))]
    stage_env, crossed = {"input": x}, []
    for start, stop in g.stage_slices(alloc):
        stage = jax.jit(lambda p, e, a=start, b=stop: g.apply_range(p, e, a, b, backend="xla"))
        stage_env = stage(params, stage_env)
        crossed.append(sorted(stage_env))
    assert crossed[0] == ["s1b1_add", "s1b2_ln"]  # branch and residual stream
    (out,) = stage_env.values()
    np.testing.assert_allclose(np.asarray(out), np.asarray(whole), rtol=1e-5, atol=1e-6)


def test_fused_interpret_matches_xla_route_every_major_node(tiny):
    """The Pallas kernels (interpret mode) of every major node, the GELU
    epilogue of each pw1 and the 7x7 depthwise kernel included, on the
    xla route's own inputs."""
    g, params, x, env = tiny
    kb = KernelBackend("pallas_fused", interpret=True)
    for n in g.major_nodes():
        got = g._apply_node(n, params, env, backend=kb)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(env[n.name]), rtol=RTOL, atol=ATOL,
            err_msg=n.name,
        )
    assert kb.fallbacks == {}  # the 7x7 convs run the depthwise kernel


@pytest.mark.parametrize(
    "hw,c,k,cout,stride,pad",
    [(8, 16, 1, 64, 1, 0), (16, 3, 4, 24, 4, 0), (8, 24, 2, 48, 2, 0)],
    ids=["pw1", "stem", "downsample"],
)
def test_conv_fused_gelu_matches_route_ref(hw, c, k, cout, stride, pad):
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((2, hw, hw, c)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((k, k, c, cout)) / np.sqrt(k * k * c), jnp.float32)
    b = jnp.asarray(rng.standard_normal((cout,)) * 0.1, jnp.float32)
    for act in ("gelu", "none"):
        got = conv2d_fused(x, w, b, stride=stride, pad=pad, act=act, interpret=True)
        want = fused_route_ref(x, w, b, stride=stride, pad=pad, act=act)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=RTOL, atol=ATOL)


def test_small_convnext_serves_like_apply():
    """``convnext()`` builds a small one, and ``serve()`` plans, stages and
    serves it (the fused route off the chip is XLA's lowering) with the
    xla route's probabilities."""
    from repro.serving import serve

    g = convnext((1, 1, 2, 1), (8, 16, 24, 32), classes=10, input_shape=(32, 32, 3))
    params = jax.jit(g.init)(jax.random.PRNGKey(2))
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(3), (6, 32, 32, 3)), np.float32)
    server = serve(g, params=params, batch_size=4, backend="pallas_fused")
    try:
        tickets = [server.submit(img) for img in x]
        got = np.stack([np.asarray(t.result(timeout=120)).reshape(-1) for t in tickets])
    finally:
        server.stop()
    want = np.asarray(jax.jit(lambda p, x: g.apply(p, x, backend="xla"))(params, x))
    assert got.shape == (6, 10)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)
