"""Training driver: ``python -m repro.launch.train --arch <id> [...]``.

Single-host it runs a real (reduced or full) config on the local devices;
with ``--dryrun-mesh`` it only verifies lowering (see dryrun.py for the
full matrix).  Fault tolerance: checkpoint/restart supervisor + straggler
accounting from repro.runtime.train_loop.
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp

from repro.configs import get_config, reduced_config
from repro.data import SyntheticLMDataset
from repro.launch.compile_cache import enable_compile_cache
from repro.optim import adamw, warmup_cosine
from repro.runtime.steps import make_train_step, model_for
from repro.runtime.train_loop import TrainLoopConfig, run_with_restarts


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="train the smoke-scale config (default); "
                         "--no-reduced trains the registered config as "
                         "published")
    ap.add_argument("--scale", type=int, default=1,
                    help="multiplier on the reduced config width/depth")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train")
    ap.add_argument("--save-every", type=int, default=50)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--backend", choices=("xla", "pallas"), default=None,
                    help="engine backend: pallas routes the kernel families"
                         " (and their scheduled backward walks) through the"
                         " engine; default keeps the process config")
    ap.add_argument("--fused", choices=("auto", "on", "off"), default=None,
                    help="fused-lowering policy for engine dispatches,"
                         " forward and backward (DESIGN.md §10-11)")
    args = ap.parse_args()
    enable_compile_cache()

    if args.backend is not None or args.fused is not None:
        from repro.core.config import configure
        overrides = {}
        if args.backend is not None:
            overrides["backend"] = args.backend
        if args.fused is not None:
            overrides["fused"] = args.fused
        configure(**overrides)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced_config(
            cfg,
            d_model=64 * args.scale,
            d_ff=128 * args.scale,
            num_layers=max(2, 2 * len(cfg.block_pattern)) * args.scale,
        )
    model = model_for(cfg)
    params = model.init(jax.random.PRNGKey(0), cfg)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"arch={cfg.name} params={n_params:,}")

    opt = adamw(warmup_cosine(args.lr, args.steps // 10, args.steps))
    opt_state = opt.init(params)
    step_fn = jax.jit(make_train_step(cfg, opt,
                                      microbatches=args.microbatches),
                      donate_argnums=(0, 1))

    ds = SyntheticLMDataset(cfg.vocab_size, args.seq, args.batch)

    def batch_fn(step):
        hb = ds.host_batch(step)
        return {k: jnp.asarray(v) for k, v in hb.items()}

    loop = TrainLoopConfig(total_steps=args.steps, ckpt_dir=args.ckpt_dir,
                           save_every=args.save_every)

    def log(step, m):
        print(f"step {step:5d} loss={m['loss']:.4f} nll={m['nll']:.4f} "
              f"gnorm={m['grad_norm']:.2f} dt={m['step_seconds']*1e3:.0f}ms")

    out = run_with_restarts(lambda: (params, opt_state), step_fn, batch_fn,
                            loop, log_fn=log)
    first = out["metrics"][0]["nll"]
    last = out["metrics"][-1]["nll"]
    floor = ds.unigram_floor_nats()
    print(f"nll: {first:.3f} -> {last:.3f} (structure floor ~{floor:.3f}, "
          f"uniform {jnp.log(cfg.vocab_size):.3f}); "
          f"stragglers={out['stragglers']} restarts={out['restarts']}")
    # Engine provenance: which families dispatched, and whether gradients
    # flowed through the scheduled backward walks (DESIGN.md §11).
    for fam, s in sorted(out.get("engine_stats", {}).items()):
        if s["launches"] or s["launches_bwd"]:
            print(f"engine[{fam}]: launches={s['launches']} "
                  f"launches_bwd={s['launches_bwd']} "
                  f"plan_hits={s['plan_hits']} "
                  f"plan_hits_bwd={s['plan_hits_bwd']}")


if __name__ == "__main__":
    main()
