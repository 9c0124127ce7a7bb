"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines, followed after each phase
by per-family engine counters (cache traffic + plan provenance + traced
launch counts, ``engine/<phase>/<family>`` rows).  Counters are reset at
phase boundaries with ``engine.reset_stats(entries=False)`` — caches stay
warm — so every table is per-phase, not cumulative.

  table1  — per-dtype matmul throughput (Table I)
  fig1    — mesh scaling efficiency from dry-run records (Fig 1)
  fig23   — data-movement staging strategies (Figs 2/3)
  fig45   — alignment / edge-handling strategies (Figs 4/5)
  fig7    — homogeneous vs heterogeneous blocking (Fig 7)
  fig89   — small-GEMM sweep vs the vendor (XLA) baseline (Figs 8/9),
            incl. fused-vs-multi-launch deltas (BENCH_gemm_fused.json)
  grouped — scheduled grouped GEMM: fused single-launch vs pad/scatter
            deltas + launch counts (BENCH_grouped_fused.json)
  flash   — scheduled flash attention: fused causal-pruned walk vs the
            dense grid, deltas + skipped-tile counts
            (BENCH_flash_fused.json)
  train   — fused-VJP vs reference-autodiff train-step time on a small
            LM config, plus per-family gradient deltas and backward
            launch counts (BENCH_train.json)
  serve   — continuous-batching Poisson trace through the paged serving
            runtime (DESIGN.md §12): tokens/s + p50/p99 per-token
            latency + the flat-launch-count proof (BENCH_serve.json)
  quant   — the low-precision axis (DESIGN.md §13): int8/W8A16 vs f32
            GEMM throughput + wire-byte savings on the fig89 shapes,
            plus the W8A16 + KV-int8 serving tokens/s delta
            (BENCH_quant.json)
  mesh    — mesh-aware expert dispatch (DESIGN.md §14): gathered vs
            distributed (all_to_all) grouped-GEMM step time, comm bytes
            and launches-per-shard on a mesh of this process's devices
            (BENCH_mesh.json)

``--smoke`` is the CI job (interpret mode): it runs the fig89 sweep plus
the grouped, flash, train, serve, quant and mesh suites at reduced size,
exercising the fused single-launch GEMM, the scheduled grouped-GEMM and
flash paths, the scheduled backward walks (DESIGN.md §11), the
continuous-batching decode path (DESIGN.md §12), the quantized
execution axis (DESIGN.md §13) *and* the mesh-aware expert dispatch
(DESIGN.md §14) end-to-end on every PR, still emitting
``BENCH_gemm_fused.json`` + ``BENCH_grouped_fused.json`` +
``BENCH_flash_fused.json`` + ``BENCH_train.json`` + ``BENCH_serve.json``
+ ``BENCH_quant.json`` + ``BENCH_mesh.json``.  After the suites it runs
the fused-ranking regression gate over ``BENCH_gemm_fused.json``:
any entry where the planner chose fused but the measured fused/multi
speedup is < 0.9 fails the job.
"""
import argparse
import json
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset, e.g. fig7,fig89")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced-size CI run of the GEMM sweep "
                         "(fused path end-to-end)")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (table1_throughput, fig1_scaling, fig23_bandwidth,
                            fig45_alignment, fig7_blocking, fig89_gemm_sweep,
                            flash_fused, grouped_fused, mesh_overlap,
                            quant_gemm, serve_trace, train_step)
    suites = {
        "table1": table1_throughput.run,
        "fig1": fig1_scaling.run,
        "fig23": fig23_bandwidth.run,
        "fig45": fig45_alignment.run,
        "fig7": fig7_blocking.run,
        "fig89": fig89_gemm_sweep.run,
        "grouped": grouped_fused.run,
        "flash": flash_fused.run,
        "train": train_step.run,
        "serve": serve_trace.run,
        "quant": quant_gemm.run,
        "mesh": mesh_overlap.run,
    }
    if args.smoke:
        if args.only:
            ap.error("--smoke selects its own suite; drop --only")
        suites = {"fig89": lambda: fig89_gemm_sweep.run(smoke=True),
                  "grouped": lambda: grouped_fused.run(smoke=True),
                  "flash": lambda: flash_fused.run(smoke=True),
                  "train": lambda: train_step.run(smoke=True),
                  "serve": lambda: serve_trace.run(smoke=True),
                  "quant": lambda: quant_gemm.run(smoke=True),
                  "mesh": lambda: mesh_overlap.run(smoke=True)}
    chosen = args.only.split(",") if args.only else list(suites)
    print("name,us_per_call,derived")
    from repro.core import engine
    for name in chosen:
        # Per-phase counters: zero the stats (keeping every cache warm) so
        # each phase's table reports its own traffic, not the cumulative
        # run — `entries=False` avoids charging a phase for rebuilding
        # kernels an earlier phase already built.
        engine.reset_stats(entries=False)
        suites[name]()
        _emit_engine_stats(name, engine)
    if args.smoke:
        _check_fused_ranking()


def _check_fused_ranking() -> None:
    """Regression gate (DESIGN.md §8): fail the smoke run when the
    planner *chose* fused on an entry whose measured fused/multi speedup
    is < 0.9 — a misranked lowering, not just a slow one."""
    with open("BENCH_gemm_fused.json") as f:
        entries = json.load(f)["entries"]
    bad = {label: e["speedup"] for label, e in sorted(entries.items())
           if e.get("chosen_fused") and e.get("speedup") is not None
           and e["speedup"] < 0.9}
    if bad:
        for label, speedup in bad.items():
            print(f"FUSED-RANKING REGRESSION: {label}: planner chose fused "
                  f"but measured fused/multi speedup = {speedup}",
                  file=sys.stderr)
        sys.exit(1)
    print(f"fused_ranking_gate,0,entries={len(entries)};violations=0")


def _emit_engine_stats(phase: str, engine) -> None:
    """Per-family plan/kernel cache traffic, plan provenance and traced
    launch counts for one phase (the paper's dispatch-layer view)."""
    for fam, c in sorted(engine.stats().items()):
        print(f"engine/{phase}/{fam},0,"
              f"plan_hits={c['plan_hits']};plan_misses={c['plan_misses']};"
              f"kernel_hits={c['kernel_hits']};"
              f"kernel_misses={c['kernel_misses']};"
              f"kernel_evictions={c['kernel_evictions']};"
              f"launches={c['launches']};"
              f"launches_bwd={c['launches_bwd']};"
              f"plan_src_model={c['plan_source_model']};"
              f"plan_src_autotuned={c['plan_source_autotuned']};"
              f"plan_src_tuned_cache={c['plan_source_tuned_cache']};"
              f"autotune_timings={c['autotune_timings']};"
              f"comm_bytes={c['comm_bytes']};"
              f"collective_launches={c['collective_launches']}")


if __name__ == '__main__':
    main()
