"""Mesh-aware expert dispatch: gathered vs distributed step time (§14).

The comm-charged planner (DESIGN.md §14) arbitrates two executions of the
same expert-parallel grouped GEMM on a model mesh:

  * gathered     — all-gather the expert weights, every shard runs the
                   full expert set over its token slice (XLA moves the
                   weights; the engine issues no collectives);
  * distributed  — keep the weight shards, ``all_to_all`` the activations
                   so each shard runs only its E/s local experts.

This suite times BOTH strategies with pinned plans on two configs — one
where big weight panels make the all-gather (and the E-panel kernel walk)
expensive, one where a large token stream makes the ``all_to_all`` pair
the dominant wire cost — records what the planner chose, and writes the
whole table to ``BENCH_mesh.json`` (step time, per-strategy comm bytes,
collective and kernel launches per shard, cross-strategy max error).

The suite runs in this process on the devices JAX has — one ``model``
axis over all of them, whose size must divide the configs' 8 experts and
token groups — so it never starts a process that would need the chip.
Each result records the platform, device kind and device count.
"""
import json
import sys

MESH_JSON = "BENCH_mesh.json"

# (label, nt, e, cap, k, n): "weights_heavy" keeps the token stream tiny
# against 8 big k*n expert panels — gathered walks all 8 panels per shard
# while distributed walks one; "tokens_heavy" streams enough rows through
# small panels that the paired all_to_all is the dominant wire cost.
CONFIGS = [
    ("weights_heavy", 8, 8, 16, 256, 256),
    ("tokens_heavy", 64, 8, 64, 64, 64),
]
SMOKE_CONFIGS = [
    ("weights_heavy", 8, 8, 16, 128, 128),
    ("tokens_heavy", 32, 8, 32, 64, 64),
]


def run(smoke: bool = False) -> None:
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.common import emit, time_fn
    from repro.core import engine
    from repro.core.blocking import mesh_local_desc, plan_grouped
    from repro.core.descriptor import GroupedGemmDescriptor, MeshSpec
    from repro.launch.mesh import make_test_mesh
    from repro.runtime.shardlib import use_mesh

    devices = jax.devices()
    ndev = len(devices)

    rng = np.random.default_rng(0)
    iters, warmup = (2, 1) if smoke else (5, 2)
    configs = SMOKE_CONFIGS if smoke else CONFIGS
    out = {"devices": ndev, "platform": devices[0].platform,
           "device_kind": devices[0].device_kind,
           "mode": "smoke" if smoke else "full", "configs": {}}

    with use_mesh(make_test_mesh(1, ndev)):
        for label, nt, e, cap, k, n in configs:
            desc = GroupedGemmDescriptor(
                t=nt * e * cap, k=k, n=n, num_experts=e,
                mesh=MeshSpec("model", ndev))
            chosen = plan_grouped(desc)
            x4 = jnp.asarray(rng.standard_normal((nt, e, cap, k)),
                             jnp.float32)
            w = jnp.asarray(rng.standard_normal((e, k, n)), jnp.float32)

            entry = {"nt": nt, "e": e, "cap": cap, "k": k, "n": n,
                     "planner_choice": chosen.comm}
            ys = {}
            for comm in ("gathered", "distributed"):
                # Pin the strategy: plan the LOCAL sub-problem it executes,
                # then re-attach the global mesh descriptor + strategy tag.
                pin = dataclasses.replace(
                    plan_grouped(mesh_local_desc(desc, comm)),
                    desc=desc, comm=comm)
                f = jax.jit(lambda x4, w, p=pin: engine.dispatch(
                    desc, x4, w, None, plan=p))
                before = {kk: vv for kk, vv in
                          engine.stats().get("grouped_gemm", {}).items()}
                us = time_fn(f, x4, w, iters=iters, warmup=warmup)
                after = engine.stats()["grouped_gemm"]
                ys[comm] = f(x4, w)
                # Trace-time counters: the jit traces the dispatch exactly
                # once across the whole timing loop, so the delta is the
                # per-step traffic of one traced call.
                entry[comm] = {
                    "us": round(us, 1),
                    "comm_bytes": after["comm_bytes"]
                    - before.get("comm_bytes", 0),
                    "collective_launches": after["collective_launches"]
                    - before.get("collective_launches", 0),
                    "launches_per_shard": after["launches"]
                    - before.get("launches", 0),
                }
                emit(f"mesh/{label}/{comm}", us,
                     f"comm_bytes={entry[comm]['comm_bytes']};"
                     f"collective_launches="
                     f"{entry[comm]['collective_launches']};"
                     f"launches_per_shard="
                     f"{entry[comm]['launches_per_shard']}")
            err = float(jnp.max(jnp.abs(ys["gathered"] - ys["distributed"])))
            entry["max_err"] = err
            entry["speedup_distributed"] = round(
                entry["gathered"]["us"] / entry["distributed"]["us"], 3)
            emit(f"mesh/{label}/choice", 0,
                 f"planner={chosen.comm};"
                 f"speedup_distributed={entry['speedup_distributed']};"
                 f"max_err={err:.1e}")
            out["configs"][label] = entry

    with open(MESH_JSON, "w") as fh:
        json.dump(out, fh, indent=1, sort_keys=True)
    emit("mesh/json", 0, f"wrote={MESH_JSON};devices={ndev}")


if __name__ == "__main__":
    run("--smoke" in sys.argv)
