"""Launch/roofline/tpu_pipeit/serving tests, including a subprocess-based
8-fake-device mini dry-run (device count must be set before jax init, so
it cannot run in this process)."""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.configs import ARCHS, SHAPES, get_config
from repro.core.tpu_pipeit import layer_costs, plan_stages, time_matrix, tpu_platform
from repro.roofline.analysis import collective_bytes

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------- tpu_pipeit
@pytest.mark.slow  # ~130s over 10 archs: heavy stage-planning sweeps
@pytest.mark.parametrize("arch", ARCHS)
def test_plan_stages_valid_partition(arch):
    cfg = get_config(arch)
    plan, stats = plan_stages(cfg, SHAPES["decode_32k"], n_chips=16)
    flat = [l for st in plan.allocation for l in st]
    assert flat == list(range(cfg.n_layers))
    used = sum(n for _, n in plan.pipeline.stages)
    assert used <= 16
    assert stats["pipeline_steps_per_s"] >= stats["tp_baseline_steps_per_s"] * 0.999


def test_layer_costs_cover_all_layers():
    cfg = get_config("deepseek-moe-16b")
    costs = layer_costs(cfg, 4096)
    assert len(costs) == cfg.n_layers
    # MoE layers stream far more weight bytes than they compute actively
    dense, moe = costs[0], costs[-1]
    assert moe.weight_bytes > dense.weight_bytes


def test_stage_time_speedup_regimes():
    """The TPU analogue of paper Fig. 11.  Two regimes:

    - weight-streaming decode of a BIG layer: near-linear concave speedup
      with chips (the 'more cores help' regime),
    - token-heavy train step of a SMALL layer: collectives swamp the
      speedup (<1) — exactly the regime where pipeline stages of few chips
      beat wide tensor parallelism (the paper's Fig. 3 collapse analogue).
    """
    big = get_config("command-r-plus-104b")
    T = time_matrix(layer_costs(big, 32768), 16, tokens_per_step=8)
    t = [T[0][("c", n)] for n in range(1, 17)]
    sp = [t[0] / x for x in t]
    assert sp[-1] > 8  # near-linear for weight streaming
    assert sp[-1] <= 16.0
    gains = [b - a for a, b in zip(sp, sp[1:])]
    assert gains[0] >= gains[-1] - 1e-9  # concave (diminishing returns)

    small = get_config("smollm-360m")
    T2 = time_matrix(layer_costs(small, 4096), 16, tokens_per_step=65536)
    t2 = [T2[0][("c", n)] for n in range(1, 17)]
    assert t2[0] < t2[15]  # 16-way TP of a small layer is SLOWER than 1 chip


# ------------------------------------------------------- collective parse
def test_collective_bytes_parser():
    hlo = textwrap.dedent("""
      %ag = f32[16,128]{1,0} all-gather(%x), replica_groups={}
      %ar.1 = bf16[8,256]{1,0} all-reduce(%y), to_apply=%add
      %tuple = (f32[4,4]{1,0}, f32[4,4]{1,0}) all-to-all(%a, %b)
      %other = f32[999]{0} add(%p, %q)
    """)
    out = collective_bytes(hlo)
    assert out["all-gather"] == 16 * 128 * 4
    assert out["all-reduce"] == 8 * 256 * 2
    assert out["all-to-all"] == 2 * 16 * 4
    assert "add" not in out


# ------------------------------------------------------ serving engine
def test_pipelined_engine_matches_single_stage():
    import jax
    import jax.numpy as jnp

    from repro.cnn import MODELS
    from repro.core import Pipeline, PipelinePlan
    from repro.serving import PipelinedGraphEngine, SingleStageEngine

    graph = MODELS["squeezenet"]()
    params = graph.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    images = [
        jnp.asarray(rng.standard_normal((1, *graph.input_shape)), jnp.float32)
        for _ in range(4)
    ]
    w = len(graph.major_nodes())
    single = SingleStageEngine(graph, params)
    r1 = single.run(images)
    plan = PipelinePlan(
        Pipeline((("B", 4), ("s", 4))),
        (tuple(range(0, w // 2)), tuple(range(w // 2, w))),
    )
    engine = PipelinedGraphEngine(graph, params, plan)
    r2 = engine.run(images)
    assert r2["throughput"] > 0
    for a, b in zip(r1["outputs"], r2["outputs"]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)


# -------------------------------------------------- mini multi-device run
@pytest.mark.parametrize("shape_kind", ["train", "decode"])
def test_mini_dryrun_8_fake_devices(shape_kind):
    """Lower+compile a reduced arch on a (2, 4) mesh in a subprocess (the
    real dry-run path at toy scale, incl. shardings and shard_map MoE)."""
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, dataclasses
        from repro.configs import get_config
        from repro.configs.shapes import InputShape
        from repro.models import abstract_params, MeshCtx
        from repro.optim import adamw_init
        from repro.launch.mesh import batch_axes
        from repro.launch.shardings import (param_specs, opt_specs, batch_specs,
                                            cache_specs, to_named)
        from repro.launch.specs import input_specs
        from repro.launch.steps import make_train_step, make_serve_step

        cfg = get_config("olmoe-1b-7b").reduced()
        cfg = dataclasses.replace(cfg, d_model=256, n_heads=4, n_kv_heads=4,
                                  head_dim=64, grad_accum=1)
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        ctx = MeshCtx(mesh=mesh, batch_axes=batch_axes(mesh))
        params_abs = abstract_params(cfg)
        pspecs = param_specs(cfg, params_abs, mesh)
        shape = InputShape("t", 64, 4, "{shape_kind}")
        specs = input_specs(cfg, shape)
        with mesh:
            if "{shape_kind}" == "train":
                opt_abs = jax.eval_shape(adamw_init, params_abs)
                c = jax.jit(make_train_step(cfg, ctx),
                    in_shardings=(to_named(pspecs, mesh),
                                  to_named(opt_specs(cfg, opt_abs, pspecs), mesh),
                                  to_named(batch_specs(cfg, specs["batch"], mesh), mesh)),
                ).lower(params_abs, opt_abs, specs["batch"]).compile()
            else:
                cspecs = cache_specs(cfg, specs["caches"], mesh)
                c = jax.jit(make_serve_step(cfg, ctx),
                    in_shardings=(to_named(pspecs, mesh), to_named(cspecs, mesh),
                                  None, None),
                ).lower(params_abs, specs["caches"], specs["tokens"], specs["pos"]).compile()
        assert c.cost_analysis() is not None
        print("OK", c.memory_analysis().temp_size_in_bytes)
    """)
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    res = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True, text=True,
        timeout=420,
    )
    assert res.returncode == 0, res.stderr[-2000:]
    assert "OK" in res.stdout
