import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()

"""Multi-pod dry-run: prove the distribution config is coherent.

For every (architecture x input-shape) cell, on the single-pod (16,16)
mesh AND the multi-pod (2,16,16) mesh:

    jax.jit(step, in_shardings=..., out_shardings=...)
        .lower(**input_specs).compile()

must succeed; we record ``memory_analysis()`` (fits-per-chip proof),
``cost_analysis()`` (per-device FLOPs/bytes) and the collective schedule
parsed from the optimized HLO — the roofline analysis
(``repro.launch.roofline``) reads these JSON records.

Usage:
    python -m repro.launch.dryrun --arch qwen3-0.6b --shape train_4k --mesh pod
    python -m repro.launch.dryrun --all            # every cell, resumable
"""
import argparse
import json
import re
import subprocess
import sys
import time
import traceback
from typing import Dict, Optional

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "experiments", "dryrun")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
    "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
    "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")


def parse_collectives(hlo_text: str) -> Dict[str, Dict[str, float]]:
    """Sum per-device result bytes of every collective op in optimized HLO."""
    out = {c: {"count": 0, "bytes": 0.0} for c in _COLLECTIVES}
    array_re = re.compile(r"(bf16|f64|f32|f16|s64|u64|s32|u32|s16|u16|s8|u8|pred|c64|c128)"
                          r"\[([0-9,]*)\]")
    for line in hlo_text.splitlines():
        m = re.search(r"=\s+(.+?)\s+(" + "|".join(_COLLECTIVES) + r")(-start|-done)?\(",
                      line)
        if not m or (m.group(3) == "-done"):
            continue  # -done carries the same type as -start; count once
        result_type, op = m.group(1), m.group(2)
        nbytes = 0.0
        for dm in array_re.finditer(result_type):
            dt, dims = dm.group(1), dm.group(2)
            n = 1
            if dims:
                for d in dims.split(","):
                    n *= int(d)
            nbytes += n * _DTYPE_BYTES[dt]
        out[op]["count"] += 1
        out[op]["bytes"] += nbytes
    return out


def pick_optimizer(cfg):
    from repro.optim import adamw, scalable_adamw, warmup_cosine
    sched = warmup_cosine(3e-4, 1000, 100000)
    if cfg.param_count() > 100e9:
        # ≥100B: true Adafactor (no momentum, factored v) — the T5/PaLM
        # recipe; optimizer state is O(sqrt(params)).
        return scalable_adamw(sched, use_momentum=False)
    if cfg.param_count() > 10e9:
        return scalable_adamw(sched)
    return adamw(sched)


def pick_microbatches(cfg, suite) -> int:
    """Gradient-accumulation factor per arch (activation-memory knob).

    Chosen so peak per-device memory fits 16 GB HBM on the single-pod
    mesh (see EXPERIMENTS.md §Dry-run memory table)."""
    if suite.kind != "train":
        return 1
    act_cost = cfg.d_model * cfg.num_layers
    if cfg.num_experts:
        act_cost *= 2  # dispatch buffers
    if act_cost > 500_000:   # grok-1 class
        return 4
    if act_cost > 150_000:   # starcoder2 / phi3.5-moe / recurrentgemma class
        return 2
    return 1


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             save: bool = True) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import get_config, shape_for, input_specs
    from repro.configs.shapes import cell_applicable
    from repro.launch.mesh import make_production_mesh, mesh_chips
    from repro.runtime import sharding as shd
    from repro.runtime.shardlib import use_mesh
    from repro.runtime import steps as steps_lib

    cfg = get_config(arch)
    suite = shape_for(shape_name)
    record = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
              "kind": suite.kind, "params": cfg.param_count(),
              "active_params": cfg.active_param_count()}

    skip = cell_applicable(cfg, suite)
    if skip:
        record.update(status="skip", reason=skip)
        return _finish(record, save)

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multipod"))
    chips = mesh_chips(mesh)
    record["chips"] = chips
    t0 = time.time()

    with use_mesh(mesh):
        pshapes = steps_lib.param_shapes(cfg)
        fsdp = True
        if suite.kind != "train":
            # Serving holds bf16 weights (no optimizer): half the bytes,
            # half the FSDP-gather traffic per decode step.
            pshapes = jax.tree.map(
                lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16)
                if s.dtype == jnp.float32 else s, pshapes)
            # Serve-mode weight residency: when TP-sharded bf16 weights fit
            # per-chip, skip FSDP entirely — weights stay resident and the
            # per-step all-gathers disappear (EXPERIMENTS.md §Perf).
            msize = mesh.shape.get("model", 1)
            tp_resident_gb = 2.0 * cfg.param_count() / msize / 2**30
            fsdp = tp_resident_gb > 8.0
        pspecs = shd.param_pspecs(pshapes, cfg, mesh, fsdp=fsdp)
        p_shardings = shd.to_named(mesh, pspecs)
        ispecs = input_specs(cfg, suite)
        bspecs = shd.batch_pspecs(ispecs, mesh)
        b_shardings = shd.to_named(mesh, bspecs)

        if suite.kind == "train":
            optimizer = pick_optimizer(cfg)
            oshapes = steps_lib.opt_state_shapes(cfg, optimizer, pshapes)
            ospecs = shd.opt_pspecs(oshapes, pshapes, cfg, mesh)
            o_shardings = shd.to_named(mesh, ospecs)
            step_fn = steps_lib.make_train_step(
                cfg, optimizer, microbatches=pick_microbatches(cfg, suite))
            jitted = jax.jit(
                step_fn,
                in_shardings=(p_shardings, o_shardings, b_shardings,
                              NamedSharding(mesh, P())),
                out_shardings=(p_shardings, o_shardings, None),
                donate_argnums=(0, 1),
            )
            args = (pshapes, oshapes, ispecs,
                    jax.ShapeDtypeStruct((), jnp.int32))
        elif suite.kind == "prefill":
            cshapes = steps_lib.cache_shapes(cfg, suite.global_batch,
                                             suite.seq_len)
            cspecs = shd.cache_pspecs(cshapes, cfg, mesh)
            c_shardings = shd.to_named(mesh, cspecs)
            step_fn = steps_lib.make_prefill_step(cfg, capacity=suite.seq_len)
            jitted = jax.jit(step_fn,
                             in_shardings=(p_shardings, b_shardings),
                             out_shardings=(None, c_shardings, None))
            args = (pshapes, ispecs)
        else:  # decode
            cshapes = steps_lib.cache_shapes(cfg, suite.global_batch,
                                             suite.seq_len)
            cspecs = shd.cache_pspecs(cshapes, cfg, mesh)
            c_shardings = shd.to_named(mesh, cspecs)
            step_fn = steps_lib.make_serve_step(cfg)
            in_sh = [p_shardings, c_shardings,
                     b_shardings["tokens"], b_shardings["pos"]]
            args = [pshapes, cshapes, ispecs["tokens"], ispecs["pos"]]
            if cfg.encoder_decoder:
                in_sh.append(b_shardings["enc_out"])
                args.append(ispecs["enc_out"])
            jitted = jax.jit(step_fn,
                             in_shardings=tuple(in_sh),
                             out_shardings=(None, c_shardings, None),
                             donate_argnums=(1,))
            args = tuple(args)

        lowered = jitted.lower(*args)
        compiled = lowered.compile()

    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis()
    hlo = compiled.as_text()
    from repro.launch.hlo_cost import analyze as hlo_analyze
    walk = hlo_analyze(hlo)
    # Stash compressed HLO so cost-model refinements re-analyze without
    # recompiling the cell.
    import gzip
    os.makedirs(RESULTS_DIR, exist_ok=True)
    with gzip.open(os.path.join(
            RESULTS_DIR, f"{arch}__{shape_name}__{mesh_kind}.hlo.gz"),
            "wt") as f:
        f.write(hlo)
    record.update(
        status="ok",
        compile_seconds=round(time.time() - t0, 1),
        memory={
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "peak_per_device": ma.argument_size_in_bytes +
                ma.temp_size_in_bytes + ma.output_size_in_bytes -
                ma.alias_size_in_bytes,
        },
        # trip-count-aware walker (repro.launch.hlo_cost): XLA's module
        # cost_analysis counts while bodies once, undercounting scans.
        cost={
            "flops_per_device": walk["flops"],
            "bytes_per_device": walk["bytes"],
            "xla_flops_unscaled": ca.get("flops", 0.0),
            "xla_bytes_unscaled": ca.get("bytes accessed", 0.0),
        },
        collectives=walk["collectives"],
        collective_bytes_per_device=walk["collective_bytes"],
    )
    # memory_analysis() proves it fits; the walker feeds §Roofline.
    print(f"[{arch} x {shape_name} x {mesh_kind}] compiled in "
          f"{record['compile_seconds']}s")
    print("  memory_analysis:", record["memory"])
    print("  cost_analysis:", record["cost"])
    print("  collectives:", {k: v for k, v in walk["collectives"].items()
                             if v["count"]})
    return _finish(record, save)


def _finish(record: dict, save: bool) -> dict:
    if save:
        os.makedirs(RESULTS_DIR, exist_ok=True)
        name = f"{record['arch']}__{record['shape']}__{record['mesh']}.json"
        with open(os.path.join(RESULTS_DIR, name), "w") as f:
            json.dump(record, f, indent=2)
    return record


def all_cells():
    from repro.configs import list_configs, SHAPES
    for arch in list_configs():
        for shape in SHAPES:
            for mesh in ("pod", "multipod"):
                yield arch, shape, mesh


def run_all(resume: bool = True, subprocess_mode: bool = True):
    os.makedirs(RESULTS_DIR, exist_ok=True)
    failures = []
    for arch, shape, mesh in all_cells():
        name = f"{arch}__{shape}__{mesh}.json"
        path = os.path.join(RESULTS_DIR, name)
        if resume and os.path.exists(path):
            with open(path) as f:
                rec = json.load(f)
            if rec.get("status") in ("ok", "skip"):
                continue
        if subprocess_mode:
            ret = subprocess.run(
                [sys.executable, "-m", "repro.launch.dryrun",
                 "--arch", arch, "--shape", shape, "--mesh", mesh],
                env=dict(os.environ),
                capture_output=True, text=True, timeout=3600)
            if ret.returncode != 0:
                failures.append((arch, shape, mesh))
                _finish({"arch": arch, "shape": shape, "mesh": mesh,
                         "status": "error",
                         "error": (ret.stderr or "")[-4000:]}, save=True)
                print(f"FAIL [{arch} x {shape} x {mesh}]:\n{ret.stderr[-2000:]}")
            else:
                print(ret.stdout.strip().splitlines()[0]
                      if ret.stdout.strip() else f"ok {arch} {shape} {mesh}")
        else:
            try:
                run_cell(arch, shape, mesh)
            except Exception:
                failures.append((arch, shape, mesh))
                _finish({"arch": arch, "shape": shape, "mesh": mesh,
                         "status": "error",
                         "error": traceback.format_exc()[-4000:]}, save=True)
    print(f"\ndry-run sweep done; {len(failures)} failures: {failures}")
    return failures


def reanalyze_all():
    """Re-walk stashed HLO with the current cost model (no recompiles)."""
    import gzip
    from repro.launch.hlo_cost import analyze as hlo_analyze
    n = 0
    for arch, shape, mesh in all_cells():
        base = f"{arch}__{shape}__{mesh}"
        jpath = os.path.join(RESULTS_DIR, base + ".json")
        hpath = os.path.join(RESULTS_DIR, base + ".hlo.gz")
        if not (os.path.exists(jpath) and os.path.exists(hpath)):
            continue
        with open(jpath) as f:
            rec = json.load(f)
        if rec.get("status") != "ok":
            continue
        with gzip.open(hpath, "rt") as f:
            walk = hlo_analyze(f.read())
        rec["cost"]["flops_per_device"] = walk["flops"]
        rec["cost"]["bytes_per_device"] = walk["bytes"]
        rec["collectives"] = walk["collectives"]
        rec["collective_bytes_per_device"] = walk["collective_bytes"]
        with open(jpath, "w") as f:
            json.dump(rec, f, indent=2)
        n += 1
    print(f"re-analyzed {n} cells")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", choices=("pod", "multipod"), default="pod")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--no-resume", action="store_true")
    ap.add_argument("--reanalyze", action="store_true",
                    help="re-walk stashed HLO with the current cost model")
    args = ap.parse_args()
    if args.reanalyze:
        reanalyze_all()
        return
    if args.all:
        failures = run_all(resume=not args.no_resume)
        sys.exit(1 if failures else 0)
    if not args.arch or not args.shape:
        ap.error("--arch and --shape required (or --all)")
    rec = run_cell(args.arch, args.shape, args.mesh)
    sys.exit(0 if rec["status"] in ("ok", "skip") else 1)


if __name__ == "__main__":
    main()
