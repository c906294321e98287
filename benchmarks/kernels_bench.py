"""Kernel route micro-benchmarks -> BENCH_kernels.json.

Times every unique conv geometry of VGG-16 and MobileNet on the two
kernel routes (`repro.kernels.backend`):

    xla          im2col patch matrix in HBM + jnp matmul (the reference)
    pallas_fused implicit-GEMM fused conv (+autotuner blocks on TPU); off
                 TPU the fused XLA lowering — direct conv, fused epilogue

plus an interpret-mode (bm, bn, bk) sweep on two small descriptors (the
only place the Pallas kernel itself can be timed off-TPU) comparing the
autotuner's pick against the untuned default blocks.

Output: ``BENCH_kernels.json`` in the repo root (not committed: its
times are those of the host that ran it) — one record per (layer
geometry, backend): op, dims (N, K, M), backend, best block config, best
time, GFLOP/s.  The CSV rows summarize.
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.cnn import MODELS
from repro.kernels.autotune import (
    ConvAutotuner,
    _best_of_k,
    candidate_blocks,
    descriptor_key,
)
from repro.kernels.backend import finish_act, resolve_backend
from repro.kernels.conv_fused import conv2d_fused

from .common import fmt_row, write_bench_json

REPEATS = 3
BACKENDS = ("xla", "pallas_fused")


def _best_of(fn, *args):
    # shared warm-then-min timing (one implementation, tuner + bench)
    return _best_of_k(lambda: jax.block_until_ready(fn(*args)), REPEATS)


def _unique_conv_descs(model):
    seen, out = set(), []
    for d in MODELS[model]().descriptors():
        if d.kind != "conv" or d.groups != 1:
            continue
        key = descriptor_key(d)
        if key not in seen:
            seen.add(key)
            out.append(d)
    return out


def _route_records(model, tuner):
    rng = np.random.default_rng(0)
    kbs = {name: resolve_backend(name, tuner=tuner if name == "pallas_fused" else None)
           for name in BACKENDS}
    records = []
    for d in _unique_conv_descs(model):
        g = d.gemm_dims()
        x = jnp.asarray(rng.standard_normal((1, d.i_h, d.i_w, d.i_d)), jnp.float32)
        w = jnp.asarray(
            rng.standard_normal((d.f_h, d.f_w, d.i_d, d.ofm)) * 0.05, jnp.float32
        )
        b = jnp.zeros((d.ofm,), jnp.float32)
        for name, kb in kbs.items():
            # finish_act applies the ReLU routes that don't fuse it, so
            # every backend is timed on identical total work
            fn = jax.jit(
                lambda x, w, b, kb=kb, d=d: finish_act(
                    kb.conv2d(d.name, x, w, b, stride=d.stride, pad=d.pad, act="relu")
                )
            )
            t = _best_of(fn, x, w, b)
            entry = tuner.entry(d) if name == "pallas_fused" else None
            records.append({
                "op": "conv2d", "model": model, "layer": d.name,
                "dims": {"N": g.N, "K": g.K, "M": g.M},
                "backend": name,
                "blocks": (
                    {k: entry[k] for k in ("bm", "bn", "bk")}
                    if entry and entry.get("bm") else None
                ),
                "time_us": t * 1e6,
                "gflops": g.flops / t / 1e9,
            })
    return records


def _interpret_sweep_records():
    """The Pallas kernel itself, interpret mode, tuned vs default blocks
    on small descriptors — the only off-TPU place block choice is real."""
    from repro.core.descriptors import conv_descriptor

    rng = np.random.default_rng(1)
    records = []
    for d in (
        conv_descriptor("sweep_8x8x16", 8, 16, 3, 32),
        conv_descriptor("sweep_14x14x8", 14, 8, 1, 64),
    ):
        g = d.gemm_dims()
        x = jnp.asarray(rng.standard_normal((1, d.i_h, d.i_w, d.i_d)), jnp.float32)
        w = jnp.asarray(
            rng.standard_normal((d.f_h, d.f_w, d.i_d, d.ofm)) * 0.1, jnp.float32
        )
        b = jnp.zeros((d.ofm,), jnp.float32)
        ow = d.output_shape()[0]
        cands = candidate_blocks(ow, d.ofm, d.i_d)
        default_cfg = cands[0]  # candidate_blocks puts the untuned heuristic first
        timed = {}
        for cfg in cands:
            timed[cfg] = _best_of(
                lambda cfg=cfg: conv2d_fused(
                    x, w, b, stride=d.stride, pad=d.pad, act="relu",
                    interpret=True, **cfg.as_kwargs(),
                )
            )
        tuned_cfg = min(timed, key=timed.get)
        for tag, (cfg, t) in (
            ("tuned", (tuned_cfg, timed[tuned_cfg])),
            # the untuned heuristic is always among the candidates, so the
            # comparison shares one timing run (no double-timing jitter)
            ("default", (default_cfg, timed[default_cfg])),
        ):
            records.append({
                "op": f"conv_fused_interpret_{tag}", "model": "sweep",
                "layer": d.name, "dims": {"N": g.N, "K": g.K, "M": g.M},
                "backend": "pallas_interpret",
                "blocks": {"bm": cfg.bm, "bn": cfg.bn, "bk": cfg.bk},
                "time_us": t * 1e6, "gflops": g.flops / t / 1e9,
            })
    return records


def run():
    tuner = ConvAutotuner()  # per-platform JSON cache next to the module
    records = []
    for model in ("vgg16", "mobilenet"):
        records.extend(_route_records(model, tuner))
    records.extend(_interpret_sweep_records())

    write_bench_json(
        "BENCH_kernels.json",
        {"platform": jax.default_backend(), "records": records},
    )

    rows = []
    for model in ("vgg16", "mobilenet"):
        per = {n: {} for n in BACKENDS}
        for r in records:
            if r["model"] == model:
                per[r["backend"]][r["layer"]] = r["time_us"]
        layers = sorted(per["xla"])
        fused_vs_xla = sum(per["pallas_fused"][l] < per["xla"][l] for l in layers)
        tot = {n: sum(per[n].values()) for n in BACKENDS}
        rows.append(
            fmt_row(
                f"kernels_bench_{model}",
                tot["pallas_fused"] / max(len(layers), 1),
                f"xla={tot['xla']/1e3:.2f}ms "
                f"fused={tot['pallas_fused']/1e3:.2f}ms "
                f"fused_beats_xla={fused_vs_xla}/{len(layers)} "
                f"(unique conv geometries; BENCH_kernels.json)",
            )
        )
    sweep = [r for r in records if r["op"].startswith("conv_fused_interpret")]
    tuned = {r["layer"]: r["time_us"] for r in sweep if r["op"].endswith("_tuned")}
    default = {r["layer"]: r["time_us"] for r in sweep if r["op"].endswith("default")}
    won = sum(tuned[l] <= default[l] for l in tuned)
    rows.append(
        fmt_row(
            "kernels_bench_autotune_sweep", sum(tuned.values()) / max(len(tuned), 1),
            f"tuned<=default on {won}/{len(tuned)} interpret descriptors",
        )
    )
    return rows
