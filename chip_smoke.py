#!/usr/bin/env python3
"""Smoke run of the CNN serving path on one TPU chip.

    python chip_smoke.py

Builds ``serve("vgg16", backend="pallas_fused", batch_size=8)`` at the
full published VGG-16 width (224x224x3 input, 138 M parameters, random
weights from a seed), submits seeded images, waits on every ticket, and
checks that:

* every request is answered with a finite [1000] output;
* each output agrees with a plain float32 reference (``Graph.apply`` on
  the im2col + jnp route at ``jax.default_matmul_precision("highest")``)
  within ``REL_TOL``, and top-1 agrees;
* the fused Pallas kernels really ran: every stage executable contains a
  ``tpu_custom_call`` and the kernel backend recorded no fallbacks.

It exits non-zero when JAX finds no TPU (there is no CPU fallback) and on
any failed check.  The last line of standard output is a JSON record of
the device, printed only when every check passed.  This is a smoke run,
not a benchmark: the seconds it prints include compilation.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

MODEL = "vgg16"
BATCH = 8
N_REQUESTS = 3 * BATCH
SEED = 0
# Outputs are compared as max|y - ref| / max|ref| per request.  The fused
# kernels contract f32 operands at the MXU's default precision, which may
# round them to bf16 (relative rounding 2^-9, about 2e-3) where the
# reference runs full f32; over VGG-16's 16 stacked conv/fc layers that
# compounds to the 1e-2 range.  5e-2 leaves room above it and stays far
# below the O(1) error of a wrong window, tile or epilogue.
REL_TOL = 5e-2


def fail(msg: str) -> None:
    raise SystemExit(f"chip_smoke: FAIL: {msg}")


def require_tpu():
    import jax

    devices = jax.devices()
    dev = devices[0]
    print(
        f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}",
        flush=True,
    )
    if dev.platform != "tpu":
        fail(f"no TPU found (JAX platform is {dev.platform!r})")
    return dev, len(devices)


def check_kernels(server) -> int:
    """Every stage executable holds a Pallas TPU kernel, and the fused
    backend fell back to XLA for no node.  Returns the stage count."""
    import jax
    import jax.numpy as jnp

    env = {"input": jnp.zeros((BATCH, *server.graph.input_shape), jnp.float32)}
    for i, fn in enumerate(server._stage_fns):
        if "tpu_custom_call" not in fn.lower(server.params, env).as_text():
            fail(f"stage {i} executable has no tpu_custom_call")
        env = fn(server.params, env)
    jax.block_until_ready(env)
    if server.backend.fallbacks:
        fail(f"fused backend fell back to XLA for {server.backend.fallbacks}")
    return len(server._stage_fns)


def reference(graph, params, images: np.ndarray) -> np.ndarray:
    """Plain f32 forward pass of the graph on the legacy route."""
    import jax

    with jax.default_matmul_precision("highest"):
        fn = jax.jit(graph.apply)
        return np.concatenate([
            np.asarray(fn(params, images[i:i + BATCH]))
            for i in range(0, len(images), BATCH)
        ])


def main() -> None:
    dev, count = require_tpu()

    from repro.compile_cache import use_compile_cache
    from repro.serving import serve

    print(f"compile cache: {use_compile_cache()}", flush=True)
    t0 = time.perf_counter()
    server = serve(MODEL, backend="pallas_fused", batch_size=BATCH, seed=SEED)
    try:
        build_s = time.perf_counter() - t0
        print(
            f"model={MODEL} input={server.graph.input_shape} batch={BATCH} "
            f"route=pallas_fused stages={len(server._stage_fns)} "
            f"build_and_compile_s={build_s:.2f}",
            flush=True,
        )
        n_stages = check_kernels(server)
        print(f"kernels: tpu_custom_call in {n_stages}/{n_stages} stage "
              "executables, fallbacks=none", flush=True)

        rng = np.random.default_rng(SEED)
        images = rng.standard_normal(
            (N_REQUESTS, *server.graph.input_shape)
        ).astype(np.float32)
        t1 = time.perf_counter()
        tickets = [server.submit(img) for img in images]
        outs = np.stack([
            np.asarray(t.result(timeout=600)).reshape(-1) for t in tickets
        ])
        serve_s = time.perf_counter() - t1
    finally:
        server.stop()
    print(f"requests answered: {len(outs)}/{N_REQUESTS} in {serve_s:.2f}s "
          "(smoke, not a benchmark)", flush=True)
    if outs.shape != (N_REQUESTS, 1000) or not np.isfinite(outs).all():
        fail(f"bad outputs: shape {outs.shape}, finite={np.isfinite(outs).all()}")

    ref = reference(server.graph, server.params, images).reshape(N_REQUESTS, -1)
    scale = np.abs(ref).max(axis=1)
    rel_err = np.abs(outs - ref).max(axis=1) / scale
    agree = outs.argmax(axis=1) == ref.argmax(axis=1)
    # a top-1 flip is excused only where the reference's own top-2 gap
    # is inside the tolerance band
    top2 = np.sort(ref, axis=1)[:, -2:]
    gap = (top2[:, 1] - top2[:, 0]) / scale
    print(f"max error vs f32 reference: {rel_err.max():.3e} "
          f"(max|y-ref|/max|ref| per request, tolerance {REL_TOL:.0e}); "
          f"top-1 agreement {int(agree.sum())}/{N_REQUESTS}", flush=True)
    if not rel_err.max() <= REL_TOL:
        fail(f"max error {rel_err.max():.3e} exceeds {REL_TOL:.0e}")
    if not (agree | (gap <= REL_TOL)).all():
        fail(f"top-1 disagrees outside the tolerance band: {np.flatnonzero(~agree)}")

    print(json.dumps({
        "ok": True,
        "device": {"platform": dev.platform, "kind": dev.device_kind, "count": count},
    }))


if __name__ == "__main__":
    main()
