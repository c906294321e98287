"""Compile the serving path's Pallas kernels for a described TPU v5e.

Nothing runs here: each test compiles for a chip that is described, not
attached, so the chip's compiler refuses what it would refuse on the
chip (tiling rules, unsupported in-kernel ops, VMEM) at no chip time.
A passing compile says nothing about results or times.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU compiler's library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.cnn import MODELS
from repro.kernels.autotune import candidate_blocks
from repro.kernels.conv_fused import conv2d_fused, matmul_fused
from repro.kernels.dwconv import dwconv2d_fused

BATCH = 8  # the serving micro-batch chip_smoke.py runs


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache off
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


def _compile_conv(one_chip, hw, cin, k, cout, stride, pad, act="relu", **blocks):
    def f(x, w, b):
        return conv2d_fused(
            x, w, b, stride=stride, pad=pad, act=act, interpret=False, **blocks
        )

    return jax.jit(f).lower(
        _spec((BATCH, hw, hw, cin), one_chip),
        _spec((k, k, cin, cout), one_chip),
        _spec((cout,), one_chip),
    ).compile()


@pytest.mark.parametrize(
    "hw,cin,k,cout,stride,pad,act",
    [
        (224, 3, 3, 64, 1, 1, "relu"),     # VGG-16 conv1_1 (two column tiles)
        (56, 256, 3, 256, 1, 1, "relu"),   # VGG-16 conv3_2
        (14, 512, 3, 512, 1, 1, "relu"),   # VGG-16 conv5_2
        (56, 256, 1, 512, 2, 0, "relu"),   # ResNet-50 res3a_proj, 1x1 stride 2
        (224, 3, 7, 64, 2, 3, "relu"),     # ResNet-50 conv1, 7x7 stride 2
        (227, 3, 11, 96, 4, 0, "relu"),    # AlexNet conv1, 11x11 stride 4
        (224, 3, 4, 96, 4, 0, "none"),     # ConvNeXt-T stem, 4x4 stride 4
        (56, 96, 2, 192, 2, 0, "none"),    # ConvNeXt-T ds2, 2x2 stride 2
        (56, 96, 1, 384, 1, 0, "gelu"),    # ConvNeXt-T s1 pw1, erf in the epilogue
    ],
    ids=["vgg16-conv1_1", "vgg16-conv3_2", "vgg16-conv5_2",
         "resnet50-res3a_proj", "resnet50-conv1", "alexnet-conv1",
         "convnext_tiny-stem", "convnext_tiny-ds2", "convnext_tiny-s1_pw1"],
)
def test_conv_fused_compiles(one_chip, hw, cin, k, cout, stride, pad, act):
    compiled = _compile_conv(one_chip, hw, cin, k, cout, stride, pad, act=act)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize(
    "hw,cin,k,cout,stride",
    [(224, 3, 4, 96, 4), (56, 96, 2, 192, 2)],
    ids=["convnext_tiny-stem", "convnext_tiny-ds2"],
)
def test_autotune_candidates_compile_where_stride_is_kernel(one_chip, hw, cin, k, cout, stride):
    """Stride equal to the kernel and no padding: every block the sweep
    offers for ConvNeXt-T's patchify stem and downsample compiles."""
    ow = (hw - k) // stride + 1
    for c in candidate_blocks(ow=ow, cout=cout, cin=cin):
        _compile_conv(one_chip, hw, cin, k, cout, stride, 0, act="none", **c.as_kwargs())


def test_every_autotune_candidate_compiles(one_chip):
    """The sweep raises on a candidate that fails, so each one it offers
    must be one the chip's compiler accepts (VGG-16 conv5: split rows)."""
    cands = candidate_blocks(ow=14, cout=512, cin=512)
    assert any(c.bm < 14 for c in cands)  # a split row is among them
    for c in cands:
        _compile_conv(one_chip, 14, 512, 3, 512, 1, 1, **c.as_kwargs())


def test_matmul_fused_compiles_at_vgg16_fc6(one_chip):
    def f(a, w, b):
        return matmul_fused(a, w, b, act="relu", interpret=False)

    compiled = jax.jit(f).lower(
        _spec((BATCH, 25088), one_chip),
        _spec((25088, 4096), one_chip),
        _spec((4096,), one_chip),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize(
    "batch,hw,c,k",
    [
        (32, 56, 96, 7), (32, 28, 192, 7), (32, 14, 384, 7), (32, 7, 768, 7),
        (BATCH, 112, 32, 3),
    ],
    ids=["convnext_tiny-s1", "convnext_tiny-s2", "convnext_tiny-s3",
         "convnext_tiny-s4", "mobilenet-dw1"],
)
def test_dwconv_compiles(one_chip, batch, hw, c, k):
    """The depthwise kernel at ConvNeXt-T's four widths at the offline
    cell's batch, and at MobileNet's largest stride-1 block."""
    def f(x, w, b):
        return dwconv2d_fused(x, w, b, pad=k // 2, act="none", interpret=False)

    compiled = jax.jit(f).lower(
        _spec((batch, hw, hw, c), one_chip),
        _spec((k, k, 1, c), one_chip),
        _spec((c,), one_chip),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _serving_stages(one_chip, monkeypatch, model, batch):
    """Compile every stage program serve(model, backend="pallas_fused")
    builds, at full width: each program's compiled text, and the backend
    (its ``fallbacks``)."""
    import repro.kernels.backend as backend_mod
    import repro.kernels.config as config_mod
    from repro.core.platform import hikey970
    from repro.kernels.backend import resolve_backend
    from repro.serving.engine import build_stage_fns
    from repro.serving.planner import AutoPlanner

    # the process sees the CPU; steer the backend onto its TPU route
    monkeypatch.setattr(backend_mod, "on_tpu", lambda: True)
    monkeypatch.setattr(config_mod, "on_tpu", lambda: True)
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)

    graph = MODELS[model]()
    kb = resolve_backend("pallas_fused")
    planner = AutoPlanner(platform=hikey970(), source="synthetic", backend=kb)
    plan = planner.plan(graph, planner.time_matrix(graph))
    fns = build_stage_fns(graph, plan, backend=kb)
    assert len(fns) >= 2

    def on_chip(tree):
        return jax.tree.map(lambda a: _spec(a.shape, one_chip), tree)

    params = jax.eval_shape(graph.init, jax.random.PRNGKey(0))
    env = {"input": jax.ShapeDtypeStruct((batch, *graph.input_shape), jnp.float32)}
    texts = []
    for fn in fns:
        texts.append(fn.lower(on_chip(params), on_chip(env)).compile().as_text())
        env = jax.eval_shape(fn, params, env)
    return texts, kb


def test_vgg16_serving_stages_compile(one_chip, monkeypatch):
    """Every stage program serve("vgg16", backend="pallas_fused") builds,
    at batch 8 and full width, holds Pallas kernels and no fallback."""
    texts, kb = _serving_stages(one_chip, monkeypatch, "vgg16", BATCH)
    for i, text in enumerate(texts):
        assert "tpu_custom_call" in text, f"stage {i}"
    assert kb.fallbacks == {}


def test_convnext_tiny_serving_stages_hold_no_grouped_conv(one_chip, monkeypatch):
    """At the offline cell's batch of 32, no stage program of ConvNeXt-T
    keeps an XLA grouped conv: all 18 7x7 depthwise convs are the Pallas
    kernel's, and nothing fell back."""
    texts, kb = _serving_stages(one_chip, monkeypatch, "convnext_tiny", 32)
    for i, text in enumerate(texts):
        assert "tpu_custom_call" in text, f"stage {i}"
        assert "feature_group_count" not in text, f"stage {i}"
    assert kb.fallbacks == {}
