"""Serving driver: batched prefill + decode with KV/state caches.

Two modes (DESIGN.md §12):

  * static batch (default): ``python -m repro.launch.serve --arch <id>
    --batch 8 --prompt-len 64 --gen 32`` — prefill once, decode the
    whole batch in lock-step.
  * continuous batching: ``python -m repro.launch.serve --arch <id>
    --continuous`` — a Poisson-style request trace runs through the
    paged serving runtime (``repro.runtime.batching``); per-decode-step
    launch counts stay flat in ``engine.stats()`` while the batch
    churns, and greedy outputs are checked token-identical against the
    static path.

The registered config is served as published, with random weights from
``--seed`` held in ``cfg.dtype``; ``--reduced`` serves the smoke-scale
config instead (tests and CPU drives).

Zero-stall startup (DESIGN.md §15): ``--warm-start manifest.json``
records the dispatched descriptor population on a cold run and replays
it through ``ContinuousBatchingEngine.warmup`` on the next — combined
with ``--tuning-cache-preload`` (fleet cache) and ``--refit-model``
(fleet-fitted cost coefficients), serving then starts with every plan
resolved and every kernel built before the first request arrives.
"""
from __future__ import annotations

import argparse
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, reduced_config
from repro.core import engine
from repro.launch.compile_cache import enable_compile_cache
from repro.models.attention import DECODE_PAGE_SIZE
from repro.models.common import tree_cast
from repro.runtime.steps import (make_prefill_step, make_serve_step,
                                 model_for, repeat_rows)


def generate(cfg, params, prompts, gen_steps: int, *, capacity=None,
             decode_rows=None):
    """Greedy batched generation.  prompts: (b, s) int32.

    Returns a dict: ``tokens`` (b, gen_steps), ``prefill_seconds``,
    ``decode_seconds``, and an ``engine_stats`` snapshot (the
    launch-count provenance, mirroring ``launch.train``).  The decode
    position is carried *inside* the jitted step — the loop never
    rebuilds a host-side position scalar per token.

    ``decode_rows`` (a multiple of b) decodes each prefilled row repeated
    ``decode_rows // b`` times and returns the first copy, so the decode
    step compiles at that batch width: XLA compiles each width into its
    own program, and on a TPU bf16 programs of different widths need not
    round alike.
    """
    b, s = prompts.shape
    reps = (decode_rows or b) // b
    # Whole decode pages, so the pallas backend's decode reads the cache
    # with the serving runtime's kernel and page walk.
    capacity = capacity or -(-(s + gen_steps) // DECODE_PAGE_SIZE) \
        * DECODE_PAGE_SIZE
    prefill = jax.jit(make_prefill_step(cfg, capacity))
    serve = jax.jit(make_serve_step(cfg), donate_argnums=(1,))

    t0 = time.time()
    logits, cache, _ = prefill(params, {"tokens": prompts})
    jax.block_until_ready(logits)
    t_prefill = time.time() - t0

    if reps > 1:
        cache = repeat_rows(cache, reps)
        logits = jnp.repeat(logits, reps, axis=0)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
    pos = jnp.asarray(s, jnp.int32)
    out = [tok]
    t0 = time.time()
    for _ in range(gen_steps - 1):
        logits, cache, pos = serve(params, cache, tok, pos)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
        out.append(tok)
    jax.block_until_ready(tok)
    t_decode = time.time() - t0
    return {
        "tokens": jnp.concatenate(out, axis=1)[::reps],
        "prefill_seconds": t_prefill,
        "decode_seconds": t_decode,
        "engine_stats": engine.stats(),
    }


# Pool size of the continuous-batching serving cache, in cached tokens.
# At qwen3-0.6b's published widths a token holds 112 KiB of bf16 KV
# (28 layers x 8 KV heads x 128 x K and V), so the pool takes 1.8 GB.
SERVING_CACHED_TOKENS = 16384


def load_params(cfg, seed: int = 0):
    """Random weights for ``cfg`` from ``seed``, held in ``cfg.dtype``:
    initialised and cast once, inside one jit, so the float32 masters
    never sit on the device beside the serving copy."""
    model = model_for(cfg)
    dtype = jnp.dtype(cfg.dtype)
    return jax.jit(lambda rng: tree_cast(model.init(rng, cfg), dtype))(
        jax.random.PRNGKey(seed))


def serving_spec(max_len: int, page_size: int = DECODE_PAGE_SIZE,
                 cached_tokens: int = SERVING_CACHED_TOKENS):
    """Page-pool geometry: ``cached_tokens`` tokens in ``page_size``-token
    pages, block tables wide enough for one ``max_len``-token sequence."""
    from repro.models.attention import PageSpec
    from repro.runtime.pages import pages_for
    return PageSpec(pages_for(cached_tokens, page_size), page_size,
                    pages_for(max_len, page_size))


def run_continuous(cfg, params, requests, *, num_slots, spec,
                   warm_start=None):
    """Serve ``requests`` through the continuous-batching runtime and
    check the outputs against the static-batch path.

    Every prefill length and the decode step are compiled before the
    first request (``ContinuousBatchingEngine.warmup``), so the run's
    tokens/s excludes compilation; the result's ``compile_seconds`` is
    that warm-up.  Returns the engine's run result with a
    ``token_identical`` flag added.

    ``warm_start`` names a descriptor manifest (DESIGN.md §15): when the
    file exists, every kernel is plan-resolved and built too, and the
    result gains a ``warmup`` summary proving the serving phase ran with
    zero autotune timings and zero plan-cache misses.  When it does not
    exist yet, the run records one (``engine.save_manifest``) so the
    next start is warm."""
    from repro.runtime.batching import ContinuousBatchingEngine

    serving = ContinuousBatchingEngine(cfg, params, num_slots=num_slots,
                                       spec=spec)
    manifest = warm_start if warm_start and os.path.exists(warm_start) \
        else None
    # Prompt lengths the scheduler will prefill: fresh admissions use the
    # full prompt; re-admissions replay context-minus-one, which traces
    # lazily (rare, eviction-dependent).
    warmup = serving.warmup(prompt_lens={len(r.prompt) for r in requests},
                            manifest=manifest)
    if manifest is not None:
        # Counters reset so the serving phase's stats stand alone; plan /
        # kernel / trace caches all stay hot.
        engine.reset_stats(entries=False)
    result = serving.run(requests)
    result["compile_seconds"] = warmup["seconds"]
    if manifest is not None:
        stats = result["engine_stats"]
        warmup["post_autotune_timings"] = sum(
            v for b in stats.values() for k, v in b.items()
            if k.startswith("autotune_timings"))
        warmup["post_plan_misses"] = sum(
            v for b in stats.values() for k, v in b.items()
            if k.startswith("plan_misses"))
        result["warmup"] = warmup
    elif warm_start:
        engine.save_manifest(warm_start)

    # Oracle: each request decoded alone on the static path must emit the
    # same greedy tokens the churning batch produced.  It decodes at the
    # slot count's batch width (the request in every row), the width the
    # serving step compiles at.  ``diverged_at`` maps each request that
    # differs to the index of its first differing token.
    diverged = {}
    for r in requests:
        static = generate(cfg, params, jnp.asarray(r.prompt)[None, :],
                          r.max_new, decode_rows=num_slots)
        want = np.asarray(static["tokens"][0])
        got = result["outputs"][r.rid]
        if not np.array_equal(want, got):
            n = min(len(want), len(got))
            bad = np.flatnonzero(want[:n] != got[:n])
            diverged[r.rid] = int(bad[0]) if bad.size else n
    result["token_identical"] = not diverged
    result["diverged_at"] = diverged
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="serve the smoke-scale config (CPU drives); by "
                         "default the registered config is served as "
                         "published")
    ap.add_argument("--batch", type=int, default=8,
                    help="static batch size, or decode slots with "
                         "--continuous")
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--continuous", action="store_true",
                    help="continuous-batching mode over a Poisson trace")
    ap.add_argument("--backend", choices=["xla", "pallas"], default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tuning-cache", default=None,
                    help="read/write autotune timing cache (JSON path)")
    ap.add_argument("--tuning-cache-preload", default=None,
                    help="read-only fleet-merged cache (tools/tune.py)")
    ap.add_argument("--refit-model", default=None,
                    help="refit-model JSON overlaying fleet-fitted cost "
                         "coefficients (tools/tune.py refit)")
    ap.add_argument("--warm-start", default=None,
                    help="descriptor manifest for AOT warm-start; created "
                         "on first (cold) run, consumed on the next")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced_config(cfg)
    params = load_params(cfg, args.seed)
    engine_kw = {}
    if args.backend:
        engine_kw["backend"] = args.backend
    if args.tuning_cache is not None:
        engine_kw["tuning_cache"] = args.tuning_cache
    if args.tuning_cache_preload is not None:
        engine_kw["tuning_cache_preload"] = args.tuning_cache_preload
    if args.refit_model:
        from repro.core.config import get_config as get_engine_config
        from repro.core.machine import load_refit_model
        engine_kw["machine"] = load_refit_model(
            args.refit_model, base=get_engine_config().machine_model)
    if engine_kw:
        from repro.core import configure
        configure(**engine_kw)

    if args.continuous:
        from repro.runtime.batching import EXPERT_COUNTERS, poisson_trace
        reqs = poisson_trace(num_requests=6, rate=0.5,
                             prompt_lens=args.prompt_len, max_new=args.gen,
                             vocab_size=cfg.vocab_size, seed=args.seed)
        res = run_continuous(cfg, params, reqs, num_slots=args.batch,
                             spec=serving_spec(args.prompt_len + args.gen),
                             warm_start=args.warm_start)
        m = res["metrics"]
        print(f"arch={cfg.name} continuous: requests={m['requests']} "
              f"tokens={m['total_tokens']} decode_steps={m['decode_steps']} "
              f"evictions={m['evictions']} "
              f"tok/s={m['tokens_per_s']:.0f} "
              f"p50={m['p50_token_latency_s']*1e3:.1f}ms "
              f"p99={m['p99_token_latency_s']*1e3:.1f}ms "
              f"compile={res['compile_seconds']:.1f}s "
              f"token_identical={res['token_identical']}")
        fam = res["engine_stats"].get("flash_decode", {})
        if fam.get("launches"):
            print(f"engine[flash_decode]: launches={fam['launches']} "
                  f"(traced once; flat while the batch churned: "
                  f"{m['flash_decode_launches']} new in the run)")
        ph = m.get("phase_seconds", {})
        if ph:
            print("phases: " + " ".join(
                f"{k}={ph[k]*1e3:.1f}ms" for k in sorted(ph)
                if k not in EXPERT_COUNTERS))
            if cfg.num_experts:
                print("experts: " + " ".join(
                    f"{k}={int(ph[k])}" for k in EXPERT_COUNTERS))
        w = res.get("warmup")
        if w is not None:
            print(f"warm-start: warmed {sum(w['kernels'].values())} "
                  f"kernels + {len(w['prefill_lengths'])} prefill traces "
                  f"in {w['seconds']:.2f}s; serving phase: "
                  f"autotune_timings={w['post_autotune_timings']} "
                  f"plan_misses={w['post_plan_misses']}")
        elif args.warm_start:
            print(f"warm-start: recorded manifest -> {args.warm_start} "
                  f"(next start is warm)")
        return

    prompts = jax.random.randint(jax.random.PRNGKey(1),
                                 (args.batch, args.prompt_len), 0,
                                 cfg.vocab_size)
    res = generate(cfg, params, prompts, args.gen)
    ptput = args.batch * args.prompt_len / res["prefill_seconds"]
    dtput = args.batch * (args.gen - 1) / max(res["decode_seconds"], 1e-9)
    print(f"arch={cfg.name} generated {res['tokens'].shape} "
          f"prefill={ptput:.0f} tok/s decode={dtput:.0f} tok/s")


if __name__ == "__main__":
    main()
