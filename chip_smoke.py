#!/usr/bin/env python3
"""Bring-up smoke run on a TPU: the serving path at published widths.

    python chip_smoke.py             # one chip (the default)
    python chip_smoke.py --chips 4   # four chips: expert-parallel GEMM only

One chip: qwen3-0.6b at its published widths, random bf16 weights from
``--seed``, served by ``ContinuousBatchingEngine`` on the pallas backend
(compiled Pallas kernels, never the interpreter), then checked:

  * prefill logits under ``backend="pallas"`` against the same forward
    under ``backend="xla"``;
  * greedy tokens of the churning batch against the static ``generate``
    path, request by request.  Under pallas both decode through the same
    ``flash_decode`` kernel and page walk, so this shows the churning
    batch changes nothing, not that decode attention is right;
  * served tokens against one teacher-forced ``backend="xla"`` forward
    over prompt and served tokens: dense XLA attention, no page pool and
    no decode kernel — the independent witness of decode;
  * the compiled paged decode kernel against a float32 reference on a
    random pool of the serving geometry;
  * ``engine.stats()``: compiled ``gemm``, ``flash_attention`` and
    ``flash_decode`` launches, with the fused lowerings dispatched.

Four chips: one layer's expert bank at phi3.5-moe-42b widths through
the engine's expert-parallel grouped GEMM on a ("data", "model") = 1x4
mesh, the gathered and the distributed plan each pinned and compared
with the single-device grouped GEMM.

Every phase prints one line; the checks run after the last reading is
printed, and a failed one raises, so the script exits non-zero.  Off a
TPU it exits non-zero before any phase.  The last line of a passing run
is ``{"ok": true, "device": {...}}``.  Times printed here are bring-up
readings of one run, not benchmark results.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

ARCH = "qwen3-0.6b"
# qwen3-0.6b as published (hf:Qwen/Qwen3-0.6B config.json).
PUBLISHED = dict(num_layers=28, d_model=1024, num_heads=16, num_kv_heads=8,
                 head_dim=128, d_ff=3072, vocab_size=151936,
                 tie_embeddings=True, dtype="bfloat16",
                 kv_cache_dtype="bfloat16")
PROMPT_LENS = (128, 256, 384, 512)   # two requests of each
NEW_TOKENS = 32
SLOTS = 8
LOGITS_PROMPT = 512

# Pallas vs XLA prefill logits: both run the same bf16 forward and keep
# f32 accumulators, but sum in different orders and round to bf16 at
# different points (inside a fused kernel vs between XLA ops).  bf16 keeps
# 8 significant bits, so each disagreeing rounding is a relative error
# up to 2**-8; the logits pass through 28 layers of such roundings on the
# residual stream.  The bound allows 8 of them compounded: relative L2
# error of the logits <= 2**-5.
LOGITS_REL_L2 = 2.0 ** -5
# Paged decode kernel vs a float32 reference on bf16 inputs: the kernel
# rounds the probabilities to bf16 before the PV product and the output
# once more, each a relative error up to 2**-9; the bound allows four.
DECODE_REL_L2 = 2.0 ** -7
# Served tokens vs a teacher-forced XLA forward: if each XLA logit is
# within d of the served (pallas) one, the served greedy token's XLA
# logit is within 2d of the XLA maximum.  d = 2**-3 is 2.2x the largest
# pallas-vs-xla prefill logit difference measured on the chip (5.8e-2);
# a wrong page, mask or position makes the served tokens near-random
# under the XLA logits, several units below the maximum.
WITNESS_MARGIN = 2.0 ** -2

# phi3.5-moe-42b (hf:microsoft/Phi-3.5-MoE-instruct): 16 experts,
# d_model 4096, d_ff 6400; one layer's up-projection bank, bf16, over
# 4 token groups x capacity 256 dispatch slots.
EP_DIMS = dict(experts=16, k=4096, f=6400, groups=4, cap=256)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


# ---------------------------------------------------------------------------
# One chip: serving
# ---------------------------------------------------------------------------

def make_requests(vocab: int, seed: int, prompt_lens=PROMPT_LENS,
                  new_tokens: int = NEW_TOKENS, copies: int = 2):
    """``copies`` requests per prompt length, random tokens and Poisson
    arrivals (one per scheduler tick on average) from ``seed``."""
    from repro.runtime.batching import Request
    rng = np.random.default_rng(seed)
    lens = [L for _ in range(copies) for L in prompt_lens]
    arrivals = np.cumsum(rng.exponential(1.0, len(lens)))
    return [Request(rid=i, prompt=rng.integers(0, vocab, L).astype(np.int32),
                    max_new=new_tokens, arrival=float(t))
            for i, (L, t) in enumerate(zip(lens, arrivals))]


def serve_phase(cfg, params, requests, *, slots: int = SLOTS):
    """Serve ``requests`` on the pallas backend; returns the run result
    (with ``token_identical`` from the static-path oracle)."""
    from repro.core import engine, use
    from repro.launch.serve import run_continuous, serving_spec
    from repro.runtime.pages import init_serving_cache
    max_len = max(len(r.prompt) + r.max_new for r in requests)
    spec = serving_spec(max_len)
    engine.reset_stats()
    with use(backend="pallas"):
        res = run_continuous(cfg, params, requests, num_slots=slots,
                             spec=spec)
    m = res["metrics"]
    kv_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(
        jax.eval_shape(functools.partial(init_serving_cache, cfg, slots,
                                         spec))))
    say("serve", f"requests={m['requests']} tokens={m['total_tokens']} "
        f"decode_steps={m['decode_steps']} evictions={m['evictions']} "
        f"slots={slots} pages={spec.num_pages}x{spec.page_size} "
        f"kv_pool={kv_bytes / 2**30:.2f}GiB")
    check(m["requests"] == len(requests), "every request finished")
    return res


def logits_phase(cfg, params, prompt_len: int, seed: int):
    """Prefill logits of one prompt under the pallas and the xla backend.
    Returns ``(rel_l2, max_abs, custom_calls)``; ``custom_calls`` counts
    Mosaic kernels in the pallas program (0 when interpreted)."""
    from repro.core import use
    from repro.runtime.steps import make_prefill_step
    tokens = jax.random.randint(jax.random.PRNGKey(seed + 1),
                                (1, prompt_len), 0, cfg.vocab_size)
    batch = {"tokens": tokens}
    out = {}
    for backend in ("pallas", "xla"):
        with use(backend=backend):
            lowered = jax.jit(make_prefill_step(cfg, prompt_len)).lower(
                params, batch)
        if backend == "pallas":
            custom_calls = lowered.as_text().count("tpu_custom_call")
        logits, _, _ = lowered.compile()(params, batch)
        out[backend] = np.asarray(logits.astype(jnp.float32))
    ref, got = out["xla"], out["pallas"]
    check(bool(np.isfinite(got).all()), "pallas logits are finite")
    rel = float(np.linalg.norm(got - ref) / np.linalg.norm(ref))
    return rel, float(np.abs(got - ref).max()), custom_calls


def witness_phase(cfg, params, requests, outputs):
    """Each request's served tokens against one ``backend="xla"`` forward
    over its prompt and served tokens (teacher forcing).  For served
    token ``i`` the margin is the XLA maximum logit minus the XLA logit
    of that token at the position that predicted it.  Returns ``(worst
    margin, tokens where XLA's argmax agrees, tokens, mean margin of all
    vocabulary entries)`` — the last is how far a random token sits."""
    from repro.core import use
    from repro.models.attention import Q_CHUNK
    from repro.runtime.steps import forward
    # One compiled width for every request, in whole chunks of the XLA
    # causal attention.
    width = max(len(r.prompt) + r.max_new for r in requests)
    width = -(-width // Q_CHUNK) * Q_CHUNK

    @jax.jit
    def margins(params, tokens, at, served):
        logits, _, _ = forward(cfg, params, {"tokens": tokens})
        lg = logits[0, at].astype(jnp.float32)                 # (n, V)
        top = lg.max(axis=-1)
        mine = jnp.take_along_axis(lg, served[:, None], axis=-1)[:, 0]
        return top - mine, jnp.mean(top[:, None] - lg)

    worst, agree, total, spread = 0.0, 0, 0, []
    for r in requests:
        served = np.asarray(outputs[r.rid], np.int32)
        seq = np.zeros((1, width), np.int32)  # causal: the tail pad is inert
        seq[0, :len(r.prompt)] = r.prompt
        seq[0, len(r.prompt):len(r.prompt) + len(served) - 1] = served[:-1]
        at = np.arange(len(served), dtype=np.int32) + len(r.prompt) - 1
        with use(backend="xla"):
            gap, mean = margins(params, jnp.asarray(seq), jnp.asarray(at),
                                jnp.asarray(served))
        gap = np.asarray(gap)
        worst = max(worst, float(gap.max()))
        agree += int((gap == 0).sum())
        total += len(served)
        spread.append(float(mean))
    return worst, agree, total, float(np.mean(spread))


def decode_phase(cfg, spec, slots: int, seed: int) -> float:
    """The compiled paged decode kernel against the plain float32
    reference (``ref_paged_decode_attention``) on a random page pool of
    the serving geometry, each slot at a random length over shuffled
    pages.  Returns the relative L2 error."""
    from repro.kernels.flash_attention import (paged_decode_attention,
                                               ref_paged_decode_attention)
    from repro.core import use
    kq, kk, kv, kt, kl = jax.random.split(jax.random.PRNGKey(seed + 2), 5)
    dt = jnp.dtype(cfg.dtype)
    pool = (spec.num_pages, spec.page_size, cfg.num_kv_heads, cfg.head_dim)
    q = jax.random.normal(kq, (slots, cfg.num_heads, cfg.head_dim), dt)
    k_pool = jax.random.normal(kk, pool, dt)
    v_pool = jax.random.normal(kv, pool, dt)
    tables = jax.random.permutation(kt, spec.num_pages)[
        :slots * spec.max_blocks].reshape(slots, spec.max_blocks)
    lengths = jax.random.randint(kl, (slots,), 1,
                                 spec.max_blocks * spec.page_size + 1)
    with use(backend="pallas"):
        got = jax.jit(paged_decode_attention)(q, k_pool, v_pool, tables,
                                              lengths)
    f32 = [x.astype(jnp.float32) for x in (q, k_pool, v_pool)]
    ref = np.asarray(jax.jit(ref_paged_decode_attention)(
        *f32, tables, lengths))
    got = np.asarray(got.astype(jnp.float32))
    return float(np.linalg.norm(got - ref) / np.linalg.norm(ref))


def kernel_phase(stats, custom_calls: int) -> bool:
    """Print the engine counters of the serving run (launches of the three
    serving families and how many ran a fused lowering) with the resolved
    interpret mode; returns that mode."""
    from repro.core import use
    from repro.core.config import get_config, resolve_interpret
    with use(backend="pallas"):
        interpret = resolve_interpret(get_config().interpret)
    parts = [f"{fam}: launches={stats.get(fam, {}).get('launches', 0)} "
             f"fused={stats.get(fam, {}).get('launches_fused', 0)}"
             for fam in ("gemm", "flash_attention", "flash_decode")]
    say("kernels", f"interpret={interpret} " + "; ".join(parts)
        + f"; pallas prefill program holds {custom_calls} tpu_custom_call")
    return interpret


def run_one_chip(seed: int) -> None:
    from repro.configs import get_config
    from repro.launch.serve import load_params, serving_spec

    t0 = time.time()
    cfg = get_config(ARCH)
    for k, v in PUBLISHED.items():
        check(getattr(cfg, k) == v, f"{ARCH}.{k} == {v} (published)")
    params = jax.block_until_ready(load_params(cfg, seed))
    leaves = jax.tree.leaves(params)
    dtypes = sorted({str(x.dtype) for x in leaves})
    n = sum(x.size for x in leaves)
    say("model", f"arch={ARCH} layers={cfg.num_layers} "
        f"d_model={cfg.d_model} heads={cfg.num_heads}/{cfg.num_kv_heads}"
        f"x{cfg.head_dim} d_ff={cfg.d_ff} vocab={cfg.vocab_size} "
        f"tied={cfg.tie_embeddings} params={n:,} weights={','.join(dtypes)} "
        f"({sum(x.nbytes for x in leaves) / 2**30:.2f}GiB) seed={seed}")
    check(dtypes == ["bfloat16"], "weights held in bf16")

    requests = make_requests(cfg.vocab_size, seed)
    res = serve_phase(cfg, params, requests)

    # Every reading is printed before any check runs, so a failing run
    # still reports all of them.
    rel, max_abs, custom_calls = logits_phase(cfg, params, LOGITS_PROMPT,
                                              seed)
    say("correct", f"prefill logits pallas vs xla (prompt "
        f"{LOGITS_PROMPT}): rel_l2={rel:.3e} (bound {LOGITS_REL_L2:.3e}) "
        f"max_abs={max_abs:.3e}; "
        f"token_identical={res['token_identical']} "
        f"diverged_at={res['diverged_at']}")
    worst, agree, total, spread = witness_phase(cfg, params, requests,
                                                res["outputs"])
    say("correct", f"served tokens vs teacher-forced xla forward: worst "
        f"margin={worst:.3e} (bound {WITNESS_MARGIN:.3e}) "
        f"argmax_agrees={agree}/{total} "
        f"mean_vocab_margin={spread:.3e}")
    spec = serving_spec(max(len(r.prompt) + r.max_new for r in requests))
    rel_dec = decode_phase(cfg, spec, SLOTS, seed)
    say("correct", f"paged decode kernel vs float32 reference "
        f"({SLOTS} slots, {spec.num_pages} pages of {spec.page_size}): "
        f"rel_l2={rel_dec:.3e} (bound {DECODE_REL_L2:.3e})")
    interpret = kernel_phase(res["engine_stats"], custom_calls)

    m = res["metrics"]
    ph = m["phase_seconds"]
    peak = jax.devices()[0].memory_stats() or {}
    say("reading", "bring-up reading, not a benchmark: "
        f"tokens/s={m['tokens_per_s']:.1f} "
        f"wall={m['wall_seconds']:.2f}s "
        f"p50_token={m['p50_token_latency_s'] * 1e3:.1f}ms "
        f"p99_token={m['p99_token_latency_s'] * 1e3:.1f}ms "
        f"compile={res['compile_seconds']:.1f}s "
        + " ".join(f"{k}={ph[k]:.2f}s" for k in sorted(ph))
        + f" peak_hbm={peak.get('peak_bytes_in_use', 0) / 2**30:.2f}GiB"
        f" script={time.time() - t0:.0f}s")

    check(rel <= LOGITS_REL_L2, "pallas logits within the bf16 bound")
    check(res["token_identical"], "greedy tokens match the static path")
    check(worst <= WITNESS_MARGIN,
          "served tokens are greedy under the xla forward")
    check(rel_dec <= DECODE_REL_L2, "decode kernel within the bf16 bound")
    check(interpret is False, "kernels run compiled, not interpreted")
    check(custom_calls > 0, "the pallas prefill compiles Mosaic kernels")
    stats = res["engine_stats"]
    for fam in ("gemm", "flash_attention", "flash_decode"):
        check(stats.get(fam, {}).get("launches", 0) > 0, f"{fam} launched")
    for fam in ("gemm", "flash_attention"):
        check(stats[fam]["launches_fused"] > 0,
              f"{fam} dispatched its fused lowering")


# ---------------------------------------------------------------------------
# Four chips: expert-parallel grouped GEMM
# ---------------------------------------------------------------------------

def ep_phase(devices, *, experts, k, f, groups, cap, seed: int,
             dtype=jnp.bfloat16):
    """Gathered and distributed expert-parallel plans, each pinned, on a
    ``("data", "model")`` = 1 x len(devices) mesh, against the
    single-device grouped GEMM on ``devices[0]``.  Returns
    ``{plan: (max_err, bound, comm_bytes, collective_launches)}``."""
    import dataclasses

    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core import (GroupedGemmDescriptor, MeshSpec, engine,
                            mesh_local_desc, plan_grouped, use)
    from repro.core.machine import canonical_dtype
    from repro.kernels.grouped_gemm import expert_parallel_grouped_gemm
    from repro.runtime.shardlib import use_mesh

    s = len(devices)
    kx, kw = jax.random.split(jax.random.PRNGKey(seed))
    one = jax.sharding.SingleDeviceSharding(devices[0])
    x4 = jax.jit(lambda r: jax.random.normal(r, (groups, experts, cap, k),
                                             dtype), out_shardings=one)(kx)
    w = jax.jit(lambda r: (jax.random.normal(r, (experts, k, f), dtype)
                           * k ** -0.5).astype(dtype),
                out_shardings=one)(kw)

    with use(backend="pallas"):
        # Off-mesh this is the plain single-device grouped GEMM.
        ref = np.asarray(jax.jit(expert_parallel_grouped_gemm)(x4, w)
                         .astype(jnp.float32))
    # Same k products per output in f32, summed in another order, then
    # one bf16 rounding each: two bf16 ulps of the value plus the f32
    # reordering term (~sqrt(k) ulps of the largest output, 4x margin).
    eps = float(jnp.finfo(dtype).eps)
    amax = float(np.abs(ref).max())
    bound = 2 * eps * np.abs(ref) + 4 * np.sqrt(k) * np.finfo(
        np.float32).eps * amax

    mesh = Mesh(np.asarray(devices).reshape(1, s), ("data", "model"))
    desc = GroupedGemmDescriptor(t=groups * experts * cap, k=k, n=f,
                                 num_experts=experts,
                                 dtype=canonical_dtype(dtype),
                                 mesh=MeshSpec("model", s))
    x4_m = jax.device_put(x4, NamedSharding(mesh, P("model")))
    w_m = jax.device_put(w, NamedSharding(mesh, P("model")))
    out = {"planner_choice": plan_grouped(desc).comm}
    with use(backend="pallas"), use_mesh(mesh):
        for comm in ("gathered", "distributed"):
            pin = dataclasses.replace(
                plan_grouped(mesh_local_desc(desc, comm)), desc=desc,
                comm=comm)
            engine.reset_stats()
            y = jax.jit(lambda a, b, p=pin: engine.dispatch(
                desc, a, b, None, plan=p))(x4_m, w_m)
            st = engine.stats()["grouped_gemm"]
            y = np.asarray(y.astype(jnp.float32))
            err = np.abs(y - ref)
            out[comm] = dict(max_err=float(err.max()),
                             within=bool((err <= bound).all()),
                             comm_bytes=st["comm_bytes"],
                             collectives=st["collective_launches"],
                             launches=st["launches"])
    return out


def run_four_chips(seed: int) -> None:
    devices = jax.devices()
    check(len(devices) == 4, f"4 chips, found {len(devices)}")
    t0 = time.time()
    d = EP_DIMS
    say("ep", f"phi3.5-moe-42b expert bank: E={d['experts']} k={d['k']} "
        f"f={d['f']} bf16, {d['groups']} token groups x capacity "
        f"{d['cap']}, mesh data x model = 1 x {len(devices)}")
    res = ep_phase(devices, seed=seed, **d)
    for comm in ("gathered", "distributed"):
        r = res[comm]
        say("ep", f"{comm}: max_err={r['max_err']:.3e} "
            f"within_bound={r['within']} comm_bytes={r['comm_bytes']} "
            f"collectives={r['collectives']} launches={r['launches']}")
        check(r["within"], f"{comm} matches the single-device grouped GEMM")
        check(r["launches"] > 0, f"{comm} launched its kernels")
    check(res["gathered"]["comm_bytes"] == 0, "gathered issues no comm")
    check(res["distributed"]["comm_bytes"] > 0, "distributed moves bytes")
    say("reading", f"bring-up reading, not a benchmark: planner would "
        f"pick {res['planner_choice']}; script={time.time() - t0:.0f}s")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"[device] no TPU: JAX found {dev.platform}; this smoke run "
              f"has no CPU fallback", file=sys.stderr)
        return 1
    say("device", f"platform={dev.platform} kind={dev.device_kind} "
        f"count={len(jax.devices())}")

    say("device", f"compile cache: {cache_dir}")
    if args.chips == 4:
        run_four_chips(args.seed)
    else:
        run_one_chip(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
