"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch smollm-360m \
        --steps 100 --batch 8 --seq 128 [--reduced] [--ckpt-dir ckpts]

On this CPU container use --reduced (the smoke-scale variant); the full
configs are exercised through the dry-run.  With multiple devices the
production mesh shardings apply automatically.
"""
from __future__ import annotations

import argparse
import time


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()

    import jax

    from ..compile_cache import use_compile_cache
    from ..configs import get_config
    from ..data import make_batch_iterator
    from ..models import init_params
    from ..optim import adamw_init
    from .steps import make_train_step

    use_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    print(f"arch={cfg.name} layers={cfg.n_layers} d={cfg.d_model} vocab={cfg.vocab_size}")

    params = init_params(cfg, jax.random.PRNGKey(0))
    n_params = sum(p.size for p in jax.tree.leaves(params))
    print(f"params: {n_params/1e6:.1f}M")
    opt = adamw_init(params)
    step_fn = jax.jit(
        make_train_step(cfg, None, base_lr=args.lr, warmup=20, total=args.steps),
        donate_argnums=(0, 1),
    )
    it = make_batch_iterator(cfg, args.batch, args.seq, prefetch=2)

    t0 = time.perf_counter()
    tokens_done = 0
    for step in range(1, args.steps + 1):
        batch = next(it)
        params, opt, metrics = step_fn(params, opt, batch)
        tokens_done += args.batch * args.seq
        if step % args.log_every == 0 or step == 1:
            loss = float(metrics["loss"])
            dt = time.perf_counter() - t0
            print(
                f"step {step:5d}  loss {loss:7.4f}  lr {float(metrics['lr']):.2e}  "
                f"gnorm {float(metrics['grad_norm']):.2f}  "
                f"{tokens_done/dt:,.0f} tok/s"
            )
        if args.ckpt_dir and step % args.ckpt_every == 0:
            from ..checkpoint import save_checkpoint

            path = save_checkpoint(
                args.ckpt_dir, step, {"params": params},
                metadata={"arch": cfg.name, "loss": float(metrics["loss"])},
            )
            print(f"  checkpoint -> {path}")
    print(f"done in {time.perf_counter()-t0:.1f}s")


if __name__ == "__main__":
    main()
