"""Mesh-aware planning (DESIGN.md §14): comm-charged arbitration,
provenance, and 8-device expert-parallel execution.

In-process tests cover the pure model: MeshSpec validation and cache-key
participation, the per-shard local-descriptor / comm-event algebra, the
calibrated-vs-uncalibrated ``collective_seconds`` split with its ``+net``
fingerprint provenance, gathered-vs-distributed arbitration flips (with
config and with mesh size), the fused-ranking regressions the fig89
sweep caught, tuned-record round-trips carrying the strategy tag, the
``tuning_cache_preload`` warm-start tier, and the fleet-merge CLI.

The ``_MULTIDEV`` subprocess test runs the real thing: an 8-device mesh
(``--xla_force_host_platform_device_count=8`` must be set before jax
initialises, hence the subprocess) where gathered and distributed
lowerings of the same expert-parallel grouped GEMM must agree bit-for-bit
— including on ragged (partially-filled capacity) inputs — with engine
comm counters non-zero ONLY on the distributed path, gradients flowing
through the EP entry, and the MoE layer exact against the XLA oracle.
"""
import dataclasses
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (GemmDescriptor, GroupedGemmDescriptor,
                        MESH_STRATEGIES, MeshSpec, autotune, candidate_plans,
                        engine, matmul, mesh_comm_events, mesh_comm_seconds,
                        mesh_local_desc, plan_gemm, plan_grouped, use)
from repro.core.machine import CPU_HOST, TPU_V5E, MachineModel
from repro.core.microbench import (probe_all_gather, probe_all_to_all,
                                   probe_collective_latency, probe_psum)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

RNG = np.random.default_rng(11)


@pytest.fixture(autouse=True)
def fresh_engine():
    engine.reset_stats()
    yield
    engine.reset_stats()


# ---------------------------------------------------------------------------
# MeshSpec: validation + cache-key participation
# ---------------------------------------------------------------------------

def test_meshspec_validates():
    with pytest.raises(ValueError):
        MeshSpec(axis="", size=2)
    with pytest.raises(ValueError):
        MeshSpec(axis="model", size=0)


def test_descriptor_mesh_divisibility():
    with pytest.raises(ValueError):
        GemmDescriptor(m=8, n=100, k=8, mesh=MeshSpec("model", 8))
    with pytest.raises(ValueError):
        GroupedGemmDescriptor(t=64, k=8, n=8, num_experts=6,
                              mesh=MeshSpec("model", 4))
    with pytest.raises(ValueError):
        GroupedGemmDescriptor(t=66, k=8, n=8, num_experts=8,
                              mesh=MeshSpec("model", 4))


def test_mesh_participates_in_cache_key():
    base = GroupedGemmDescriptor(t=64, k=8, n=8, num_experts=8)
    m4 = dataclasses.replace(base, mesh=MeshSpec("model", 4))
    m8 = dataclasses.replace(base, mesh=MeshSpec("model", 8))
    keys = {base.cache_key(), m4.cache_key(), m8.cache_key()}
    assert len(keys) == 3, "mesh must key plans and kernels"


# ---------------------------------------------------------------------------
# Local-descriptor / comm-event algebra
# ---------------------------------------------------------------------------

def test_mesh_local_desc_grouped():
    d = GroupedGemmDescriptor(t=1024, k=64, n=32, num_experts=8,
                              mesh=MeshSpec("model", 4))
    g = mesh_local_desc(d, "gathered")
    assert (g.t, g.num_experts, g.mesh) == (256, 8, None)
    dd = mesh_local_desc(d, "distributed")
    assert (dd.t, dd.num_experts, dd.mesh) == (256, 2, None)
    with pytest.raises(ValueError):
        mesh_local_desc(d, "telepathy")


def test_mesh_local_desc_gemm():
    d = GemmDescriptor(m=64, n=256, k=32, mesh=MeshSpec("model", 4))
    assert mesh_local_desc(d, "gathered").n == 256
    assert mesh_local_desc(d, "distributed").n == 64
    assert mesh_local_desc(d, "gathered").mesh is None


def test_mesh_comm_events_bytes():
    s, e, t, k, n = 4, 8, 1024, 64, 32
    d = GroupedGemmDescriptor(t=t, k=k, n=n, num_experts=e,
                              mesh=MeshSpec("model", s))
    frac = (s - 1) / s
    (cg, bg), = mesh_comm_events(d, "gathered")
    assert cg == "all_gather" and bg == int(frac * e * k * n * 4)
    ev = mesh_comm_events(d, "distributed")
    assert [c for c, _ in ev] == ["all_to_all", "all_to_all"]
    assert ev[0][1] == int(frac * (t // s) * k * 4)
    assert ev[1][1] == int(frac * (t // s) * n * 4)
    # degenerate mesh: no wire traffic at all
    d1 = dataclasses.replace(d, mesh=MeshSpec("model", 1))
    assert mesh_comm_events(d1, "gathered") == ()


# ---------------------------------------------------------------------------
# Calibrated network model + provenance
# ---------------------------------------------------------------------------

def test_collective_seconds_uses_calibration():
    cal = dataclasses.replace(
        TPU_V5E, ici_bandwidth_gbps=100.0, collective_launch_s=2e-6,
        collective_efficiency={"all_gather": 1.0, "all_to_all": 0.5})
    nbytes = 1e8
    ag = cal.collective_seconds(nbytes, collective="all_gather")
    assert ag == pytest.approx(2e-6 + nbytes / 100e9)
    a2a = cal.collective_seconds(nbytes, collective="all_to_all")
    assert a2a == pytest.approx(2e-6 + nbytes / 50e9)
    # uncalibrated: pinned per-link napkin math, still finite + ranked
    un = TPU_V5E.collective_seconds(nbytes)
    assert un > 0 and TPU_V5E.network_calibrated is False


def test_net_provenance_in_fingerprint_and_tuning_key():
    cal = dataclasses.replace(CPU_HOST, ici_bandwidth_gbps=10.0)
    assert cal.fingerprint.endswith("+net")
    assert cal.tuning_key == CPU_HOST.name + "+net"
    assert not CPU_HOST.fingerprint.endswith("+net")
    assert CPU_HOST.tuning_key == CPU_HOST.name


def test_one_device_probes_report_uncalibrated():
    """On a 1-device host every interconnect probe must return an
    EXPLICIT 0.0 "(uncalibrated)" result — never be silently skipped —
    and ``from_probes`` must leave the network fields ``None``."""
    import jax
    if len(jax.devices()) > 1:
        pytest.skip("host unexpectedly multi-device")
    probes = {p.name: p for p in (probe_all_gather(), probe_all_to_all(),
                                  probe_psum(), probe_collective_latency())}
    assert set(probes) == {"all_gather_bw", "all_to_all_bw", "psum_bw",
                           "collective_latency"}
    for p in probes.values():
        assert p.value == 0.0 and "uncalibrated" in p.unit
    m = MachineModel.from_probes(probes, base=CPU_HOST, name="one_dev")
    assert m.ici_bandwidth_gbps is None and not m.network_calibrated
    assert m.tuning_key == "one_dev"


# ---------------------------------------------------------------------------
# Comm-charged arbitration (the §14 planner decision itself)
# ---------------------------------------------------------------------------

def _grouped_desc(nt, e, cap, k, n, s):
    return GroupedGemmDescriptor(t=nt * e * cap, k=k, n=n, num_experts=e,
                                 mesh=MeshSpec("model", s))


def test_arbitration_flips_with_config():
    # Big weight panels, few tokens: all-gathering E panels (and walking
    # all of them per shard) loses to the paired all_to_all.
    heavy_w = _grouped_desc(8, 8, 16, 512, 512, 8)
    assert plan_grouped(heavy_w).comm == "distributed"
    # Tiny panels, heavy token stream: moving activations twice costs
    # more wire time than one small weight all-gather.
    heavy_t = _grouped_desc(64, 8, 64, 64, 64, 8)
    assert plan_grouped(heavy_t).comm == "gathered"


def test_arbitration_flips_with_mesh_size():
    # Same global problem: a 2-way mesh gathers (the all_to_all payload
    # ~t/s dominates), an 8-way mesh distributes (payload shrinks 1/s^2
    # while the weight all-gather stays constant).
    small = _grouped_desc(64, 8, 16, 256, 256, 2)
    large = _grouped_desc(16, 8, 16, 256, 256, 8)
    assert plan_grouped(small).comm == "gathered"
    assert plan_grouped(large).comm == "distributed"


def test_plan_charges_comm_seconds():
    d = _grouped_desc(8, 8, 16, 256, 256, 8)
    for comm in MESH_STRATEGIES:
        pin = dataclasses.replace(plan_grouped(mesh_local_desc(d, comm)),
                                  desc=d, comm=comm)
        local = plan_grouped(mesh_local_desc(d, comm))
        assert pin.predicted_seconds() == pytest.approx(
            local.predicted_seconds() + mesh_comm_seconds(d, TPU_V5E, comm))


def test_candidate_plans_mesh_strategies():
    d = _grouped_desc(8, 8, 16, 256, 256, 8)
    cands = candidate_plans(d)
    assert [p.comm for p in cands] == list(MESH_STRATEGIES) or \
        {p.comm for p in cands} == set(MESH_STRATEGIES)
    assert len(cands) == 2
    # cheapest-first agrees with the family planner
    best = min(cands, key=lambda p: p.predicted_seconds())
    assert best.comm == plan_grouped(d).comm


def test_gemm_mesh_arbitration():
    # B column-sharded: gathered moves k*n weight bytes once, distributed
    # computes n/s locally and all-gathers the m*n output.  Tall-skinny
    # output (m << k) favors distributed; short-fat favors gathered.
    tall = GemmDescriptor(m=8, n=1024, k=4096, mesh=MeshSpec("model", 8))
    fat = GemmDescriptor(m=4096, n=1024, k=8, mesh=MeshSpec("model", 8))
    pt, pf = plan_gemm(tall), plan_gemm(fat)
    assert {pt.comm, pf.comm} == set(MESH_STRATEGIES)
    assert pt.comm == "distributed" and pf.comm == "gathered"


# ---------------------------------------------------------------------------
# Fused-ranking regressions (the fig89 smoke-gate shapes)
# ---------------------------------------------------------------------------

def test_multi_region_plans_rank_fused_vs_multi():
    """hetero_640 measured fused/multi = 0.85x: a multi-region cover's
    stitched fused walk must lose to per-region launches under the model
    too, while single-region fused keeps the paper's stance."""
    hetero = plan_gemm(GemmDescriptor(m=640, n=640, k=512),
                       force_block=(256, 256))
    assert len(hetero.regions) > 1 and hetero.fused is False
    multi = dataclasses.replace(hetero, fused=True)
    assert hetero.predicted_seconds() < multi.predicted_seconds()
    single = plan_gemm(GemmDescriptor(m=80, n=80, k=512))
    assert len(single.regions) == 1 and single.fused is True


# ---------------------------------------------------------------------------
# Tuned records + preload warm-start + fleet merge CLI
# ---------------------------------------------------------------------------

def test_plan_record_roundtrips_comm():
    d = _grouped_desc(8, 8, 16, 256, 256, 8)
    plan = plan_grouped(d)
    assert plan.comm in MESH_STRATEGIES
    rec = autotune.plan_to_record(plan)
    assert rec["comm"] == plan.comm
    back = autotune.plan_from_record(d, rec)
    assert back.comm == plan.comm
    assert (back.bm, back.bk, back.bn) == (plan.bm, plan.bk, plan.bn)


def test_tuning_cache_preload_serves_tier1(tmp_path):
    """A fleet-merged cache preloaded read-only must satisfy plans with
    zero autotune timings — the serving warm-start path (§14)."""
    path = str(tmp_path / "fleet.json")
    d = GemmDescriptor(m=80, n=80, k=64)
    pinned = plan_gemm(d, force_block=(8, 128), heterogeneous=False)
    autotune.TuningCache(path).store(TPU_V5E.tuning_key, d, pinned, 1.0,
                                     interpret=True)
    a = jnp.asarray(RNG.standard_normal((80, 64)), jnp.float32)
    b = jnp.asarray(RNG.standard_normal((64, 80)), jnp.float32)
    with use(backend="pallas", tuning_cache_preload=path):
        out = matmul(a, b)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(a) @ np.asarray(b),
                               rtol=1e-4, atol=1e-4)
    s = engine.stats()["gemm"]
    assert s["plan_source_tuned_cache"] == 1
    assert s["autotune_timings"] == 0


def test_tune_cli_merge_newest_wins(tmp_path):
    key = "v5e+net|compiled|('gemm',)"
    a, b = tmp_path / "a.json", tmp_path / "b.json"
    a.write_text(json.dumps({"version": 1, "entries": {
        key: {"us": 10.0, "ts": 100.0},
        "v5e|compiled|('gemm', 2)": {"us": 5.0, "ts": 100.0}}}))
    b.write_text(json.dumps({"version": 1, "entries": {
        key: {"us": 8.0, "ts": 200.0}}}))
    out = tmp_path / "merged.json"
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "tune.py"),
         "merge", str(out), str(a), str(b)], capture_output=True, text=True)
    assert r.returncode == 0, r.stderr
    merged = json.loads(out.read_text())["entries"]
    assert len(merged) == 2 and merged[key]["us"] == 8.0
    # export filters by machine tuning-key prefix (+net kept separate)
    only = tmp_path / "net.json"
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "tune.py"), "export",
         str(out), str(only), "--machine", "v5e+net"],
        capture_output=True, text=True)
    assert r.returncode == 0, r.stderr
    assert list(json.loads(only.read_text())["entries"]) == [key]


# ---------------------------------------------------------------------------
# 8-device execution (subprocess: forced host device count)
# ---------------------------------------------------------------------------

_MULTIDEV = r"""
import dataclasses
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (GroupedGemmDescriptor, MeshSpec, engine,
                        mesh_local_desc, plan_grouped, use)
from repro.kernels.grouped_gemm import expert_parallel_grouped_gemm
from repro.kernels.grouped_gemm.ops import _ref_ep
from repro.launch.mesh import make_test_mesh
from repro.runtime.shardlib import use_mesh

assert len(jax.devices()) == 8, jax.devices()
rng = np.random.default_rng(0)
nt, e, cap, k, f = 8, 8, 16, 64, 96
x4 = jnp.asarray(rng.standard_normal((nt, e, cap, k)), jnp.float32)
# ragged occupancy: expert j fills only j+1 of its cap slots (zeros feed
# the kernel for the empty tail exactly like real dropped-token routing)
occ = (jnp.arange(cap)[None, :] <= jnp.arange(e)[:, None]).astype(jnp.float32)
x4 = x4 * occ[None, :, :, None]
w = jnp.asarray(rng.standard_normal((e, k, f)), jnp.float32)
desc = GroupedGemmDescriptor(t=nt * e * cap, k=k, n=f, num_experts=e,
                             mesh=MeshSpec("model", 8))
ref = _ref_ep(None, x4, w)

with use(backend="pallas", interpret=True), \
     use_mesh(make_test_mesh(1, 8)):
    # --- both pinned strategies match the oracle on the ragged input ---
    # Same k products per output, summed in fp32 in an order that depends
    # on the lowering: ~sqrt(k) fp32 ulps of the largest output (4x margin).
    tol = 4 * np.sqrt(k) * np.finfo(np.float32).eps * float(jnp.abs(ref).max())
    for comm in ("gathered", "distributed"):
        pin = dataclasses.replace(plan_grouped(mesh_local_desc(desc, comm)),
                                  desc=desc, comm=comm)
        engine.reset_stats()
        y = engine.dispatch(desc, x4, w, None, plan=pin)
        err = float(jnp.max(jnp.abs(y - ref)))
        assert err <= tol, (comm, err, tol)
        s = engine.stats()["grouped_gemm"]
        assert s["launches"] == 1, (comm, s)  # fused single launch/shard
        if comm == "distributed":
            assert s["comm_bytes"] > 0 and s["collective_launches"] == 2, s
        else:
            assert s["comm_bytes"] == 0 and s["collective_launches"] == 0, s

    # --- planner selection flips across configs on THIS mesh -----------
    heavy_w = GroupedGemmDescriptor(t=8 * 8 * 16, k=512, n=512,
                                    num_experts=8, mesh=MeshSpec("model", 8))
    heavy_t = GroupedGemmDescriptor(t=64 * 8 * 64, k=64, n=64,
                                    num_experts=8, mesh=MeshSpec("model", 8))
    assert plan_grouped(heavy_w).comm == "distributed"
    assert plan_grouped(heavy_t).comm == "gathered"

    # --- EP entry point: autodiff flows (custom VJP over the oracle) ---
    def loss(w):
        return jnp.sum(expert_parallel_grouped_gemm(x4, w, axis="model"))
    g = jax.grad(loss)(w)
    g_ref = jax.grad(lambda w: jnp.sum(_ref_ep(None, x4, w)))(w)
    assert float(jnp.max(jnp.abs(g - g_ref))) < 1e-4

    # --- flagship consumer: MoE layer exact vs the XLA oracle ----------
    from repro.configs import get_config as model_config, reduced_config
    from repro.models.moe import moe_apply, moe_init
    cfg = reduced_config(model_config("phi3.5-moe-42b"), num_experts=8)
    params = moe_init(jax.random.PRNGKey(0), cfg)
    x = jnp.asarray(rng.standard_normal((8, 32, cfg.d_model)), jnp.float32)
    engine.reset_stats()
    y_mesh, aux_mesh = moe_apply(params, cfg, x)
    s = engine.stats()["grouped_gemm"]
    assert s["comm_bytes"] > 0 and s["collective_launches"] > 0, s
    assert s["launches"] == 3, s  # up/gate/down, one fused launch each

with use(backend="xla"):
    y_ref, aux_ref = moe_apply(params, cfg, x)
err = float(jnp.max(jnp.abs(y_mesh - y_ref)))
assert err < 1e-4, err
assert abs(float(aux_mesh) - float(aux_ref)) < 1e-5
print("MULTIDEV-OK")
"""


def test_eight_device_mesh_execution(tmp_path):
    script = tmp_path / "multidev.py"
    script.write_text(_MULTIDEV)
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8").strip()
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(ROOT, "src")]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    r = subprocess.run([sys.executable, str(script)], env=env,
                       capture_output=True, text=True, timeout=540)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "MULTIDEV-OK" in r.stdout
