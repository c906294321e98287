"""Serving launcher: batched prefill + decode loop for any architecture.

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m \
        --reduced --batch 4 --prompt-len 32 --gen 32
"""
from __future__ import annotations

import argparse
import time


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--reduced", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from ..compile_cache import use_compile_cache
    from ..configs import get_config
    from ..models import init_cache, init_params, prefill, serve_step

    use_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    rng = jax.random.PRNGKey(0)
    params = init_params(cfg, rng)
    b = args.batch
    extra = (cfg.n_patches or 0) + (128 if cfg.block_kind == "hymba" else 0)
    shape = (
        (b, args.prompt_len, cfg.n_codebooks) if cfg.n_codebooks else (b, args.prompt_len)
    )
    prompt = jax.random.randint(rng, shape, 0, cfg.vocab_size)
    batch = {"tokens": prompt}
    if cfg.n_patches:
        batch["patches"] = jax.random.normal(rng, (b, cfg.n_patches, 1152))

    caches = init_cache(cfg, b, max_len=args.prompt_len + extra + args.gen)
    t0 = time.perf_counter()
    _, caches = jax.jit(lambda p, bt, c: prefill(cfg, p, bt, c))(params, batch, caches)
    jax.block_until_ready(caches)
    t_prefill = time.perf_counter() - t0
    print(f"prefill: {b}x{args.prompt_len} in {t_prefill*1e3:.0f}ms")

    step = jax.jit(
        lambda p, c, t, pos: serve_step(cfg, p, c, t, pos), donate_argnums=(1,)
    )
    tok = prompt[:, -1:]
    t0 = time.perf_counter()
    generated = []
    for i in range(args.gen):
        pos = args.prompt_len + extra + i
        logits, caches = step(params, caches, tok, jnp.int32(pos))
        nxt = jnp.argmax(logits, axis=-1)
        tok = nxt[:, None, :] if cfg.n_codebooks else nxt[:, None]
        generated.append(nxt)
    jax.block_until_ready(generated)
    dt = time.perf_counter() - t0
    print(
        f"decode: {args.gen} steps x batch {b} = {args.gen*b} tokens "
        f"in {dt*1e3:.0f}ms -> {args.gen*b/dt:,.1f} tok/s"
    )
    print("sample token ids:", [int(g[0]) if g[0].ndim == 0 else g[0].tolist() for g in generated[:8]])


if __name__ == "__main__":
    main()
