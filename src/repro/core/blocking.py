"""Register/VMEM blocking planner — the paper's §IV-B adapted to TPU.

The paper's code generator owns a *palette* of accumulator register
blockings for the 4 KiB ZA array — 32x32, 16x64, 64x16 — and covers a
ragged output matrix C with a *heterogeneous* mix of them so that the
number of microkernel executions is minimized (Fig 7: 7 executions instead
of 10 for an 80x80 C), with predicate-masked edges.

On TPU the accumulator lives in VMEM and is fed by the 128x128 MXU, so the
palette is a set of (bm, bn) VMEM accumulator blocks under a fixed element
budget (the ZA-capacity analogue), aligned to the native register tiling
(sublane x 128 lanes) and ideally to the MXU edge (128).  The cost model is
the paper's, re-derived for a systolic unit:

  * every accumulator update of a (bm, bn) block with a K-panel of depth bk
    loads (bm + bn) * bk input elements — maximizing bm*bn/(bm+bn) is the
    paper's argument for square blocks (32x32 loads 64 values/update,
    16x64 loads 80);
  * masked (edge) blocks issue bm*bn MACs but only use rows*cols of them —
    utilization of the systolic array replaces predicated-lane occupancy;
  * each block execution has a fixed grid-step overhead (the analogue of
    the paper's per-microkernel-invocation cost that motivates Fig 7).

``plan_gemm`` returns a :class:`BlockingPlan`: a list of :class:`Region`
covers (interior / bottom strip / right strip / corner), each of which maps
onto one shape-specialized ``pallas_call`` in ``repro.kernels.gemm``.
"""
from __future__ import annotations

import dataclasses
import math
from typing import List, Optional, Sequence, Tuple

import jax.numpy as jnp

from .descriptor import (BIAS_EPILOGUES, FlashBwdDescriptor,
                         FlashDecodeDescriptor, FlashDescriptor,
                         GemmDescriptor, GroupedGemmBwdDescriptor,
                         GroupedGemmDescriptor, SsdChunkBwdDescriptor,
                         SsdChunkDescriptor, TransposeDescriptor)
from . import machine as machine_mod
from .machine import MachineModel, DEFAULT_MACHINE
# The flattening/predication machinery lives in the schedule layer
# (DESIGN.md §9); re-exported here for compatibility — plans *produce*
# schedules, so blocking is the schedule layer's only upstream.
from .schedule import (LANES, DecodeTileSchedule,  # noqa: F401
                       FlashTileSchedule, GroupedTileSchedule, TileSchedule,
                       ceil_div, decode_vmem_need, flash_bwd_vmem_need,
                       flash_tile_schedule,
                       flash_vmem_need, flatten_regions,
                       grouped_bwd_vmem_need, matmul_vmem_need,
                       plan_launches, round_up, sublanes, vmem_fits)

# ---------------------------------------------------------------------------
# Palette
# ---------------------------------------------------------------------------

# Accumulator element budget per kernel instance.  ZA analogue: M4 has
# 1024 fp32 accumulator elements; v5e's VMEM comfortably holds 64k fp32
# accumulator elements (256 KiB) next to double-buffered input blocks.
ACC_BUDGET_ELEMS = 256 * 256

# Candidate block edge lengths.  bn must be lane-aligned (128); bm is
# sublane-aligned with MXU-aligned values preferred.
_BM_CANDIDATES = (8, 16, 32, 64, 128, 256, 512)
_BN_CANDIDATES = (128, 256, 512, 1024)

# Per-microkernel/grid-step launch cost now lives on the machine model
# (``machine.step_overhead_s``) so calibration can replace the pinned
# default with the measured dispatch latency (DESIGN.md §7).


def palette(budget: int = ACC_BUDGET_ELEMS,
            machine: MachineModel = DEFAULT_MACHINE,
            dtype: str = "float32") -> List[Tuple[int, int]]:
    """All legal (bm, bn) accumulator blockings under ``budget`` elements.

    Mirrors the paper's {32x32, 16x64, 64x16}: the full-budget shapes here
    are {256x256, 128x512, 512x128} plus sub-budget shapes used for small
    or ragged problems (where the paper would mask most of a tile).
    """
    sub, lane = machine.reg_tile(dtype)
    shapes = []
    for bm in _BM_CANDIDATES:
        if bm % sub:
            continue
        for bn in _BN_CANDIDATES:
            if bn % lane:
                continue
            if bm * bn > budget:
                continue
            shapes.append((bm, bn))
    return shapes


# ---------------------------------------------------------------------------
# Plan datatypes
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Region:
    """A rectangular sub-block of C covered with a single blocking."""

    row0: int
    col0: int
    rows: int
    cols: int
    bm: int
    bn: int

    @property
    def grid(self) -> Tuple[int, int]:
        return (ceil_div(self.rows, self.bm), ceil_div(self.cols, self.bn))

    @property
    def num_microkernels(self) -> int:
        gm, gn = self.grid
        return gm * gn

    def issued_macs(self, k: int) -> int:
        gm, gn = self.grid
        return gm * self.bm * gn * self.bn * k

    def useful_macs(self, k: int) -> int:
        return self.rows * self.cols * k

    def input_elems(self, k: int) -> int:
        """Input traffic: paper's loads-per-update metric summed over blocks."""
        gm, gn = self.grid
        return (gm * gn) * (self.bm + self.bn) * k


@dataclasses.dataclass(frozen=True)
class BlockingPlan:
    """Planned heterogeneous region cover of one GEMM descriptor (§IV-B,
    Fig 7): the regions, the uniform K-panel depth ``bk``, and the
    ``fused`` execution-path bit (DESIGN.md §8)."""

    desc: GemmDescriptor
    regions: Tuple[Region, ...]
    bk: int
    heterogeneous: bool
    # Execute the whole plan (regions + batch) in ONE pallas_call via the
    # flattened tile schedule (DESIGN.md §8) instead of one launch per
    # region stitched with dynamic_slice / dynamic_update_slice.
    fused: bool = False
    # Provenance: "model" (analytical planner) or "autotuned" (empirically
    # timed winner, fresh or replayed from the tuning cache — DESIGN.md §7).
    plan_source: str = "model"
    # Mesh strategy (DESIGN.md §14), set only when desc.mesh is: "gathered"
    # (all-gather the sharded weights, compute the whole problem locally)
    # or "distributed" (keep weight shards, move activations/outputs).
    # The regions/bk knobs then describe the per-shard local sub-problem
    # (``mesh_local_desc``), not the global descriptor.
    comm: Optional[str] = None

    # ---- aggregate stats (paper Fig 7 metrics) -------------------------
    @property
    def num_microkernels(self) -> int:
        return sum(r.num_microkernels for r in self.regions)

    @property
    def utilization(self) -> float:
        k = self.desc.k
        issued = sum(r.issued_macs(k) for r in self.regions)
        useful = sum(r.useful_macs(k) for r in self.regions)
        return useful / max(1, issued)

    @property
    def input_elems(self) -> int:
        return sum(r.input_elems(self.desc.k) for r in self.regions)

    def predicted_seconds(self, machine: MachineModel = DEFAULT_MACHINE) -> float:
        local, comm_s = self.desc, 0.0
        if self.desc.mesh is not None and self.comm is not None:
            local = mesh_local_desc(self.desc, self.comm)
            comm_s = mesh_comm_seconds(self.desc, machine, self.comm)
        return _predict_seconds(self.regions, local, self.bk, machine,
                                fused=self.fused) + comm_s

    def tile_schedule(self) -> TileSchedule:
        """Flatten the region cover into the fused kernel's tile tables
        (delegates to the schedule layer, DESIGN.md §9).  For a mesh plan
        the schedule covers the per-shard local sub-problem — execution
        happens per shard (DESIGN.md §14)."""
        desc = self.desc
        if desc.mesh is not None and self.comm is not None:
            desc = mesh_local_desc(desc, self.comm)
        # Rows of A, C and the output share window origins: align them to
        # the narrowest of those dtypes' register tiles.
        row_align = max(8 * max(1, 4 // desc.a_wire_itemsize),
                        sublanes(desc.out_dtype))
        return flatten_regions(desc.m, desc.n, desc.k, self.bk, self.regions,
                               row_align=row_align)

    def validate(self):
        """Every C element covered exactly once (tested by hypothesis)."""
        cover = {}
        for ri, r in enumerate(self.regions):
            for i in (r.row0, r.row0 + r.rows - 1):
                for j in (r.col0, r.col0 + r.cols - 1):
                    assert 0 <= i < self.desc.m and 0 <= j < self.desc.n, (r, self.desc)
        total = sum(r.rows * r.cols for r in self.regions)
        assert total == self.desc.m * self.desc.n, (
            f"cover mismatch: {total} vs {self.desc.m * self.desc.n}")
        # overlap check on region rectangles
        rects = [(r.row0, r.col0, r.row0 + r.rows, r.col0 + r.cols) for r in self.regions]
        for i in range(len(rects)):
            for j in range(i + 1, len(rects)):
                a, b = rects[i], rects[j]
                if not (a[2] <= b[0] or b[2] <= a[0] or a[3] <= b[1] or b[3] <= a[1]):
                    raise AssertionError(f"regions overlap: {a} {b}")
        return True


# ---------------------------------------------------------------------------
# Cost model
# ---------------------------------------------------------------------------

# Calibration against BENCH_gemm_fused.json (measured fused/multi deltas).
# The bench showed the previous model over-charged the multi-launch path
# (fused vs multi predicted identically for single-region plans, yet fused
# measured 0.79x at nn_128 and 0.82x at hetero_640): fused execution is
# not free — every grid step decodes a tile-table row and the accumulator
# read-modify-writes its output window — while the measured multi-launch
# dispatch + stitch overhead is ~4x smaller than the model charged.
# These coefficients now live on :class:`MachineModel` so the offline
# refit pipeline (``tools/tune.py refit``, DESIGN.md §15) can replace the
# hand calibration with a least-squares fit of TuningCache timings; the
# module aliases keep the seed values importable.
FUSED_TILE_DECODE_S = machine_mod.DEFAULT_FUSED_TILE_DECODE_S
EXTRA_LAUNCH_FACTOR = machine_mod.DEFAULT_EXTRA_LAUNCH_FACTOR
STITCH_DISCOUNT = machine_mod.DEFAULT_STITCH_DISCOUNT


def _predict_seconds(regions: Sequence[Region], desc: GemmDescriptor, bk: int,
                     machine: MachineModel, fused: bool = False) -> float:
    """Napkin-math time model used to rank candidate plans.

    Four terms, mirroring the roofline decomposition used throughout the
    system: systolic compute on *issued* MACs (masked lanes still occupy
    the MXU — the SME predicate analogue), HBM traffic for inputs + C,
    per-grid-step overhead, and per-``pallas_call`` dispatch overhead.
    The fused path (DESIGN.md §8) pays dispatch once but adds per-step
    tile-table decode plus the accumulator's output-window re-read
    (read-modify-write); the multi-launch path pays dispatch per region
    plus the inter-region stitching traffic (``dynamic_slice`` operand
    copies and the ``zeros`` + ``dynamic_update_slice`` assembly of C).
    Both extras are calibrated against BENCH_gemm_fused.json.
    """
    k = desc.k
    # Wire itemsizes: under a quant spec (DESIGN.md §13) the staged
    # operands are the narrow dtype — the planner charges the bytes that
    # actually move, which is the whole point of the low-precision axis.
    a_sz = desc.a_wire_itemsize
    b_sz = desc.b_wire_itemsize
    out_sz = jnp.dtype(desc.out_dtype).itemsize
    issued = sum(r.issued_macs(k) for r in regions)
    compute_s = 2.0 * issued / machine.peak(desc.compute_dtype)
    traffic = sum(r.num_microkernels * (r.bm * a_sz + r.bn * b_sz) * k
                  for r in regions)
    out_elems = sum(r.rows * r.cols for r in regions)
    traffic += out_elems * out_sz * (2 if desc.accumulate else 1)
    memory_s = traffic / machine.hbm_bw
    steps = sum(r.num_microkernels for r in regions) * ceil_div(k, bk)
    launches = 1 if fused else len(regions)
    launch_s = machine.launch_overhead_s * (
        1 + (launches - 1) * machine.extra_launch_factor)
    stitch_s = 0.0
    fused_s = 0.0
    if fused:
        # Table decode per step plus the RMW re-read of each output window.
        fused_s = (steps * machine.fused_tile_decode_s
                   + out_elems * out_sz / machine.hbm_bw)
    elif len(regions) > 1:
        # Operand slices are copied in and region outputs copied out again
        # when stitching C — traffic the fused path never generates.
        stitch_bytes = sum((r.rows * a_sz + r.cols * b_sz) * k
                           for r in regions)
        stitch_bytes += 2 * out_elems * out_sz
        stitch_s = machine.stitch_discount * stitch_bytes / machine.hbm_bw
    # compute and memory overlap in the pipelined kernel: take max + overhead
    return (max(compute_s, memory_s) + steps * machine.step_overhead_s
            + launch_s + stitch_s + fused_s)


def _pick_bk(desc: GemmDescriptor, bm: int, bn: int,
             machine: MachineModel) -> int:
    """Largest K-panel depth whose double-buffered blocks fit VMEM.

    VMEM budget: acc (bm*bn fp32) + 2*(bm*bk + bk*bn) inputs.  The paper's
    analogue is the two Z-register pairs feeding FMOPA; on TPU deeper
    panels amortize the systolic pipeline, so we take the largest aligned
    bk <= K subject to VMEM.
    """
    acc_bytes = bm * bn * 4
    budget = machine.vmem_bytes // 2 - acc_bytes  # conservative half-VMEM
    if budget <= 0:
        return machine.lanes
    bk_max = budget // (2 * (desc.a_wire_itemsize * bm
                             + desc.b_wire_itemsize * bn))
    sub, lane = machine.reg_tile(desc.in_dtype)
    bk = max(lane, (bk_max // lane) * lane)
    bk = min(bk, round_up(desc.k, lane), 2048)
    return bk


# ---------------------------------------------------------------------------
# Mesh-aware communication model (DESIGN.md §14)
# ---------------------------------------------------------------------------
# A mesh descriptor (``desc.mesh is not None``) describes the GLOBAL
# problem with the weight operand sharded over ``mesh.axis``.  Each
# execution strategy reduces it to a per-shard local sub-problem plus a
# set of collectives; the planner charges both — compute/launch/stitch
# on the local descriptor via the family cost model, communication via
# ``machine.collective_seconds`` — so gathered-vs-distributed is ranked
# by the same napkin-math discipline as every tiling knob.

MESH_STRATEGIES = ("gathered", "distributed")


def mesh_local_desc(desc, comm: str):
    """The per-shard local sub-problem one strategy actually executes.

    grouped_gemm — activations token-sharded over the axis:
      * gathered: all-gather the expert weights, run the full expert set
        over the local token shard (t/s tokens, all E experts);
      * distributed: keep weight shards, all_to_all tokens to their
        expert's owner (t/s tokens, E/s local experts — capacity-uniform
        routing moves exactly the local rows).
    gemm — B column-sharded over the axis:
      * gathered: all-gather B, compute the full (m, n) locally;
      * distributed: keep the B shard, compute (m, n/s), all-gather the
        output columns.
    """
    if desc.mesh is None:
        return desc
    if comm not in MESH_STRATEGIES:
        raise ValueError(f"unknown mesh strategy {comm!r}")
    s = desc.mesh.size
    if isinstance(desc, GroupedGemmDescriptor):
        if comm == "gathered":
            return dataclasses.replace(desc, t=desc.t // s, mesh=None)
        return dataclasses.replace(desc, t=desc.t // s,
                                   num_experts=desc.num_experts // s,
                                   mesh=None)
    if comm == "gathered":
        return dataclasses.replace(desc, mesh=None)
    return dataclasses.replace(desc, n=desc.n // s, mesh=None)


def mesh_comm_events(desc, comm: str) -> Tuple[Tuple[str, int], ...]:
    """``((collective, per-device payload bytes), ...)`` one strategy
    issues around the local kernel.  Payloads follow the probe accounting
    in ``core.microbench``: bytes each device sends/receives, with the
    ring (s-1)/s factor folded in."""
    if desc.mesh is None or desc.mesh.size == 1:
        return ()
    s = desc.mesh.size
    frac = (s - 1) / s
    if isinstance(desc, GroupedGemmDescriptor):
        isz = jnp.dtype(desc.dtype).itemsize
        if comm == "gathered":
            w_sz = getattr(desc, "w_wire_itemsize", isz)
            return (("all_gather",
                     int(frac * desc.num_experts * desc.k * desc.n * w_sz)),)
        t_loc = desc.t // s
        return (("all_to_all", int(frac * t_loc * desc.k * isz)),
                ("all_to_all", int(frac * t_loc * desc.n * isz)))
    out_sz = jnp.dtype(desc.out_dtype).itemsize
    if comm == "gathered":
        return (("all_gather", int(frac * desc.k * desc.n
                                   * desc.b_wire_itemsize)),)
    return (("all_gather", int(frac * desc.m * desc.n * out_sz)),)


def mesh_comm_seconds(desc, machine: MachineModel, comm: str) -> float:
    """Total modeled communication time of one strategy under ``machine``
    (honest when network-calibrated, link-spec napkin math otherwise)."""
    return sum(machine.collective_seconds(nbytes, collective=c)
               for c, nbytes in mesh_comm_events(desc, comm))


# ---------------------------------------------------------------------------
# Planner
# ---------------------------------------------------------------------------

def fused_legal(desc: GemmDescriptor,
                machine: MachineModel = DEFAULT_MACHINE) -> bool:
    """Can this GEMM run as one fused ``pallas_call`` (DESIGN.md §8)?

    The fused kernel stages the whole per-batch-element operands (plus the
    output and the accumulator scratch) in VMEM and slides tile windows
    over them in-kernel, so it is only legal when they all fit.  Batch is a
    grid dimension — only one batch slice is resident at a time.  The
    bytes are those the kernel asks Mosaic for
    (:func:`~repro.core.schedule.matmul_vmem_need`), at the largest
    padded extents and accumulator any schedule of this GEMM can have.
    """
    quant = desc.quant is not None
    need = matmul_vmem_need(
        _row_bound(desc.m), round_up(desc.n, LANES), round_up(desc.k, LANES),
        a_isz=desc.a_wire_itemsize, b_isz=desc.b_wire_itemsize,
        out_isz=jnp.dtype(desc.out_dtype).itemsize,
        acc=(ACC_BUDGET_ELEMS // LANES, LANES), layout=desc.layout,
        accumulate=desc.accumulate, row_scales=quant,
        col_rows=int(quant) + int(desc.epilogue in BIAS_EPILOGUES))
    return vmem_fits(need, machine.vmem_bytes)


def _row_bound(extent: int) -> int:
    """Upper bound of a staged row extent: schedules pad rows to at most
    the widest sublane tile (32 rows of a 1-byte dtype)."""
    return round_up(extent, 32)


def _edge_bound(extent: int, align: int) -> int:
    """Largest tile edge :func:`_tile_candidates` offers along ``extent``."""
    return min(_TILE_HI, round_up(extent, align))


def plan_gemm(desc: GemmDescriptor,
              machine: MachineModel = DEFAULT_MACHINE,
              budget: int = ACC_BUDGET_ELEMS,
              heterogeneous: bool = True,
              force_block: Optional[Tuple[int, int]] = None) -> BlockingPlan:
    """Produce the blocking plan for one GEMM descriptor.

    ``heterogeneous=False`` reproduces the paper's baseline (Fig 7 left):
    one blocking tiles the whole matrix.  ``force_block`` pins the primary
    blocking (used by benchmarks and the perf hillclimb).  The analytical
    planner takes the paper's stance on dispatch: one kernel per GEMM —
    plans come out ``fused`` whenever the operands fit VMEM
    (:func:`fused_legal`); the autotuner refines that choice empirically.

    A mesh descriptor is planned per strategy (DESIGN.md §14): the local
    sub-problem of each strategy gets its own blocking, and the cheaper
    compute + communication total wins, recorded in ``plan.comm``.
    """
    if desc.mesh is not None:
        best = None
        for comm in MESH_STRATEGIES:
            p = plan_gemm(mesh_local_desc(desc, comm), machine, budget,
                          heterogeneous, force_block)
            p = dataclasses.replace(p, desc=desc, comm=comm)
            if best is None or (p.predicted_seconds(machine)
                                < best.predicted_seconds(machine)):
                best = p
        return best
    m, n = desc.m, desc.n
    shapes = palette(budget, machine, desc.in_dtype)
    fused = fused_legal(desc, machine)

    if force_block is not None:
        primary = force_block
    else:
        primary = _best_homogeneous(m, n, shapes, desc, machine)

    if not heterogeneous:
        regions = (Region(0, 0, m, n, *primary),)
        bk = _pick_bk(desc, *primary, machine)
        plan = BlockingPlan(desc, regions, bk, heterogeneous=False,
                            fused=fused)
        return plan

    regions = _heterogeneous_cover(m, n, primary, shapes, desc, machine)
    # Compare against the best homogeneous plan and keep the cheaper one —
    # for aligned shapes the interior cover *is* the homogeneous plan.
    bk = _pick_bk(desc, *primary, machine)
    plan = BlockingPlan(desc, tuple(regions), bk,
                        heterogeneous=len(regions) > 1, fused=fused)
    homo = BlockingPlan(desc, (Region(0, 0, m, n, *primary),), bk, False,
                        fused=fused)
    if homo.predicted_seconds(machine) < plan.predicted_seconds(machine):
        plan = homo
    # Multi-region covers pay the fused walk's per-step tile decode on
    # every region's tiles; BENCH_gemm_fused.json measured hetero shapes
    # where the stitched multi-launch path wins (hetero_640 at 0.848x).
    # The paper's one-kernel stance holds for single-region plans only —
    # for multi-region winners, compare both lowerings under the model.
    if plan.fused and len(plan.regions) > 1:
        multi = dataclasses.replace(plan, fused=False)
        if multi.predicted_seconds(machine) < plan.predicted_seconds(machine):
            plan = multi
    return plan


def _best_homogeneous(m: int, n: int, shapes, desc, machine) -> Tuple[int, int]:
    best, best_t = None, float("inf")
    for bm, bn in shapes:
        # Skip grossly oversized blocks (all-masked) unless nothing smaller.
        region = Region(0, 0, m, n, bm, bn)
        bk = _pick_bk(desc, bm, bn, machine)
        t = _predict_seconds([region], desc, bk, machine)
        if t < best_t:
            best, best_t = (bm, bn), t
    assert best is not None
    return best


def _strip_block(extent_major: int, extent_minor: int, shapes,
                 major_axis: int) -> Tuple[int, int]:
    """Pick the palette block for an edge strip.

    ``major_axis`` = 0 for the bottom strip (few rows, many cols: paper's
    16x64 analogue) and 1 for the right strip (64x16 analogue).  Choose the
    smallest block edge covering the strip thickness (minimum masking) and
    the largest perpendicular edge (minimum invocations).
    """
    best = None
    # minimal covering thickness
    thick_opts = sorted({s[major_axis] for s in shapes})
    cover = [t for t in thick_opts if t >= extent_major]
    thickness = cover[0] if cover else thick_opts[-1]
    spans = [s[1 - major_axis] for s in shapes if s[major_axis] == thickness]
    span = max(spans)
    best = (thickness, span) if major_axis == 0 else (span, thickness)
    return best


def _heterogeneous_cover(m, n, primary, shapes, desc, machine) -> List[Region]:
    bm0, bn0 = primary
    m_full, n_full = m // bm0, n // bn0
    mi, ni = m_full * bm0, n_full * bn0
    regions: List[Region] = []
    if m_full and n_full:
        regions.append(Region(0, 0, mi, ni, bm0, bn0))
    rem_m, rem_n = m - mi, n - ni
    if rem_m and ni:
        bm_s, bn_s = _strip_block(rem_m, ni, shapes, major_axis=0)
        regions.append(Region(mi, 0, rem_m, ni, bm_s, bn_s))
    if rem_n and mi:
        bm_s, bn_s = _strip_block(rem_n, mi, shapes, major_axis=1)
        regions.append(Region(0, ni, mi, rem_n, bm_s, bn_s))
    if rem_m and rem_n:
        bm_c, bn_c = _corner_block(rem_m, rem_n, shapes)
        regions.append(Region(mi, ni, rem_m, rem_n, bm_c, bn_c))
    if not regions:  # degenerate: matrix smaller than every block
        bm_c, bn_c = _corner_block(m, n, shapes)
        regions.append(Region(0, 0, m, n, bm_c, bn_c))
    return regions


def _corner_block(rows, cols, shapes) -> Tuple[int, int]:
    """Smallest palette block covering the (masked) corner."""
    covering = sorted(shapes, key=lambda s: (ceil_div(rows, s[0]) * ceil_div(cols, s[1]),
                                             s[0] * s[1]))
    return covering[0]


# ---------------------------------------------------------------------------
# Non-GEMM family planners
# ---------------------------------------------------------------------------
# Same discipline as plan_gemm: enumerate machine-legal tilings, rank them
# under the max(compute, memory) + per-step-overhead cost model, return a
# frozen plan.  These replace the hardcoded constants the kernel wrappers
# used to carry (block_q=512, bm=128/bk=512/bn=256, bt=256).

_TILE_HI = 1024


def _tile_candidates(extent: int, align: int, lo: int = 64,
                     hi: int = _TILE_HI) -> List[int]:
    """Aligned power-of-two tile edges covering [lo, hi], clipped to extent.

    An edge >= extent collapses to the aligned cover of extent itself, so
    small problems get exactly one full tile instead of a masked giant.
    """
    cands = set()
    t = lo
    while t <= hi:
        cands.add(min(t, round_up(extent, align)) if t >= extent else t)
        t *= 2
    return sorted(c for c in cands if c % align == 0 or c >= extent)


@dataclasses.dataclass(frozen=True)
class FlashPlan:
    """Planned (block_q, block_k) tiling of one flash attention descriptor.

    ``fused`` selects the scheduled single-launch lowering (DESIGN.md
    §10): the causal-aware tile table drops fully-masked k-blocks at
    plan time and ONE ``pallas_call`` walks it; the non-fused fallback is
    the dense-grid kernel that skips masked tiles with a run-time branch.
    """

    desc: FlashDescriptor
    block_q: int
    block_k: int
    # Execute via the flattened causal-aware tile table in ONE pallas_call
    # over staged whole operands (DESIGN.md §10); mirrors BlockingPlan.fused.
    fused: bool = False
    plan_source: str = "model"  # see BlockingPlan.plan_source

    def tile_schedule(self) -> FlashTileSchedule:
        """Flatten the (q, k) walk into the fused kernel's tile table
        (delegates to the schedule layer, DESIGN.md §10)."""
        d = self.desc
        return flash_tile_schedule(d.sq, d.sk, self.block_q, self.block_k,
                                   d.causal, row_align=sublanes(d.dtype))

    def predicted_seconds(self, machine: MachineModel = DEFAULT_MACHINE) -> float:
        """Cost-model estimate under ``machine`` (see
        :func:`_predict_flash_seconds`)."""
        return _predict_flash_seconds(self.desc, self.block_q, self.block_k,
                                      machine, fused=self.fused)


def flash_fused_legal(desc: FlashDescriptor,
                      machine: MachineModel = DEFAULT_MACHINE) -> bool:
    """Can this flash attention run as one scheduled ``pallas_call``?

    The fused kernel stages one batch-head slice of q/k/v and the output
    whole in VMEM (clamped ragged windows need element-granular origins,
    which BlockSpec block indices cannot express) and slides tile windows
    over them in-kernel; legal only when they fit next to the per-tile
    score/carry scratch — the bytes the kernel asks Mosaic for
    (:func:`~repro.core.schedule.flash_vmem_need`), at the largest tiles
    and padded extents any plan of this descriptor can have, with the
    LSE column the training forward drains."""
    need = flash_vmem_need(
        _row_bound(desc.sq), _row_bound(desc.sk), desc.d,
        isz=jnp.dtype(desc.dtype).itemsize,
        bq=_edge_bound(desc.sq, 32), bk=_edge_bound(desc.sk, LANES),
        lse=True)
    return vmem_fits(need, machine.vmem_bytes)


def _predict_flash_seconds(desc: FlashDescriptor, bq: int, bk: int,
                           machine: MachineModel,
                           fused: bool = False) -> float:
    """Napkin-math time model for one flash tiling (both lowerings).

    Causal skips tiles strictly above the diagonal — the heterogeneous-
    cover idea applied to the triangle.  The fused lowering only *walks*
    active tiles (the table drops the rest at plan time), while the
    dense-grid fallback pays grid-step overhead on every (q, k) pair and
    merely branches the masked ones' compute away; both pay one launch.
    """
    cq, ck = ceil_div(desc.sq, bq), ceil_div(desc.sk, bk)
    if desc.causal:
        active = sum(min(ck, ceil_div((qi + 1) * bq, bk)) for qi in range(cq))
    else:
        active = cq * ck
    steps = desc.batch_heads * (active if fused else cq * ck)
    # Issued MACs: tiles are padded to (bq, bk) — masked lanes still occupy
    # the MXU (the SME predicate analogue).
    issued = 4 * desc.batch_heads * active * bq * bk * desc.d
    compute_s = issued / machine.peak(desc.dtype)
    isz = jnp.dtype(desc.dtype).itemsize
    if fused:
        # Whole q/k/v staged once per batch-head slice; output written once.
        traffic = desc.in_bytes + desc.out_bytes
    else:
        # Each active step streams one K and one V tile; Q tiles stream
        # once per q-row of active tiles; output written once.
        traffic = desc.batch_heads * active * 2 * bk * desc.d * isz
        traffic += desc.batch_heads * cq * bq * desc.d * isz
        traffic += desc.out_bytes
    memory_s = traffic / machine.hbm_bw
    return (max(compute_s, memory_s) + steps * machine.step_overhead_s
            + machine.launch_overhead_s)


def _flash_legal(desc: FlashDescriptor,
                 machine: MachineModel) -> List[Tuple[int, int]]:
    """All VMEM-legal (block_q, block_k) pairs for one flash descriptor."""
    sub, lane = machine.reg_tile(desc.dtype)
    isz = jnp.dtype(desc.dtype).itemsize
    legal = []
    for bq in _tile_candidates(desc.sq, sub):
        for bk in _tile_candidates(desc.sk, lane):
            # VMEM: q tile + k/v tiles (double-buffered) + fp32 scratch
            # (score tile, running max/denom, output accumulator).
            vmem = (bq * desc.d + 2 * 2 * bk * desc.d) * isz
            vmem += (bq * bk + 2 * bq + bq * desc.d) * 4
            if vmem > machine.vmem_bytes // 2:
                continue
            legal.append((bq, bk))
    if not legal:  # head dim so large nothing fits: minimal legal tiles
        legal.append((sub, lane))
    return legal


def plan_flash(desc: FlashDescriptor,
               machine: MachineModel = DEFAULT_MACHINE) -> FlashPlan:
    """Pick (block_q, block_k) from VMEM/MXU constraints + the cost model.

    Like ``plan_gemm``, the analytical planner takes the paper's stance
    on dispatch: plans come out ``fused`` (single scheduled launch over
    the causal-aware tile table) whenever the staged operands fit VMEM
    (:func:`flash_fused_legal`); the autotuner refines empirically.
    """
    fused = flash_fused_legal(desc, machine)
    best = min(_flash_legal(desc, machine),
               key=lambda s: _predict_flash_seconds(desc, *s, machine=machine,
                                                    fused=fused))
    return FlashPlan(desc, *best, fused=fused)


# A paged decode grid step stages at least this many times the machine's
# per-step overhead in KV bytes, so the walk's time follows the live KV
# rather than its row count (DESIGN.md §12).
DECODE_STEP_COVER = 4


@dataclasses.dataclass(frozen=True)
class FlashDecodePlan:
    """Plan of one paged decode-attention step (DESIGN.md §12).

    The page size is fixed at cache construction; the plan's one choice
    is ``pages_per_step``, the block-table entries one grid step stages
    (:func:`decode_pages_per_step`).  Like the grouped family, the plan
    is always ``fused`` — the ragged block walk happens inside ONE
    ``pallas_call`` riding runtime tables, and the non-fused alternative
    is the model-level XLA gather path that never enters the engine."""

    desc: FlashDecodeDescriptor
    pages_per_step: int = 1
    fused: bool = True
    plan_source: str = "model"  # see BlockingPlan.plan_source

    def tile_schedule(self) -> DecodeTileSchedule:
        """The runtime-table schedule this step walks (one row per block
        of ``pages_per_step`` live pages, plus the per-slot floor)."""
        d = self.desc
        return DecodeTileSchedule(num_seqs=d.num_seqs, pages=d.pages,
                                  page_size=d.page_size,
                                  max_blocks=d.max_blocks,
                                  pages_per_step=self.pages_per_step)

    def predicted_seconds(self, machine: MachineModel = DEFAULT_MACHINE
                          ) -> float:
        """Napkin-math step time: every walked tile issues a full
        (h, block, hd) MAC pair; traffic streams each live page once
        plus the q/out rows and the prefetch tables."""
        d = self.desc
        steps = self.tile_schedule().max_tiles
        compute_s = d.flops / machine.peak(d.dtype)
        memory_s = (d.in_bytes + d.out_bytes) / machine.hbm_bw
        return (max(compute_s, memory_s) + steps * machine.step_overhead_s
                + machine.launch_overhead_s)


def decode_pages_per_step(desc: FlashDecodeDescriptor,
                          machine: MachineModel = DEFAULT_MACHINE) -> int:
    """Pages one decode grid step stages: the smallest power of two whose
    K+V bytes take :data:`DECODE_STEP_COVER` times ``step_overhead_s`` to
    read at ``hbm_bw``, capped at the block-table width and at what the
    step's double-buffered pages and score tile leave of VMEM."""
    isz = jnp.dtype(desc.dtype).itemsize
    page_bytes = 2 * desc.page_size * desc.num_kv_heads * desc.head_dim * isz
    want = DECODE_STEP_COVER * machine.step_overhead_s * machine.hbm_bw
    pps = 1
    while pps * page_bytes < want and pps < desc.max_blocks:
        pps *= 2
    pps = min(pps, desc.max_blocks)

    def fits(n):
        return vmem_fits(decode_vmem_need(
            n, page_size=desc.page_size, num_heads=desc.num_heads,
            num_kv_heads=desc.num_kv_heads, head_dim=desc.head_dim,
            isz=isz), machine.vmem_bytes)

    while pps > 1 and not fits(pps):
        pps //= 2
    return pps


def plan_flash_decode(desc: FlashDecodeDescriptor,
                      machine: MachineModel = DEFAULT_MACHINE
                      ) -> FlashDecodePlan:
    """Single-lowering planner: the pool geometry fixed the page at cache
    construction; the plan sizes the walk's step from the page bytes."""
    return FlashDecodePlan(desc, decode_pages_per_step(desc, machine))


@dataclasses.dataclass(frozen=True)
class GroupedGemmPlan:
    """Planned (bm, bk, bn) tiling of one ragged grouped GEMM, plus the
    ``fused`` execution-path bit (scheduled single launch vs pad/scatter
    — DESIGN.md §9)."""

    desc: GroupedGemmDescriptor
    bm: int
    bk: int
    bn: int
    # Execute the ragged dispatch as ONE pallas_call walking runtime tile
    # tables (DESIGN.md §9) instead of the host-side pad/scatter +
    # gather-back lowering.  Mirrors BlockingPlan.fused.
    fused: bool = False
    plan_source: str = "model"  # see BlockingPlan.plan_source
    comm: Optional[str] = None  # mesh strategy — see BlockingPlan.comm

    @property
    def local_desc(self) -> GroupedGemmDescriptor:
        """The per-shard sub-problem this plan's knobs describe: the
        descriptor itself off-mesh, ``mesh_local_desc`` under a mesh
        strategy (DESIGN.md §14)."""
        if self.desc.mesh is not None and self.comm is not None:
            return mesh_local_desc(self.desc, self.comm)
        return self.desc

    @property
    def t_padded(self) -> int:
        """Static row bound of the pad/scatter lowering: T rounded up plus
        per-group padding room."""
        d = self.local_desc
        return round_up(d.t, self.bm) + d.num_experts * self.bm

    def tile_schedule(self) -> GroupedTileSchedule:
        """The static geometry of the fused lowering (DESIGN.md §9); the
        tables themselves are runtime data built from ``group_sizes``.
        For a mesh plan this is the per-shard schedule — the fused
        single-launch property holds per shard (DESIGN.md §14)."""
        d = self.local_desc
        return GroupedTileSchedule(
            t=d.t, k=d.k, n=d.n, num_experts=d.num_experts,
            bm=min(self.bm, d.t), bk=min(self.bk, d.k), bn=min(self.bn, d.n),
            row_align=max(sublanes(d.dtype),
                          8 * max(1, 4 // getattr(d, "x_wire_itemsize",
                                                  4))))

    def predicted_seconds(self, machine: MachineModel = DEFAULT_MACHINE) -> float:
        comm_s = 0.0
        if self.desc.mesh is not None and self.comm is not None:
            comm_s = mesh_comm_seconds(self.desc, machine, self.comm)
        return _predict_grouped_seconds(self.local_desc, self.bm, self.bk,
                                        self.bn, machine,
                                        fused=self.fused) + comm_s


def grouped_fused_legal(desc: GroupedGemmDescriptor,
                        machine: MachineModel = DEFAULT_MACHINE) -> bool:
    """Can this grouped GEMM run as one scheduled ``pallas_call``?

    The fused kernel stages the whole token block and output in VMEM
    (clamped row windows need element-granular origins, which BlockSpec
    block indices cannot express) plus one double-buffered expert weight
    panel; legal only when they all fit — the bytes the kernel asks
    Mosaic for (:func:`~repro.core.schedule.matmul_vmem_need`), at the
    largest tiles and padded extents any plan of this descriptor can have.
    """
    isz = jnp.dtype(desc.dtype).itemsize
    quant = getattr(desc, "quant", None) is not None
    need = matmul_vmem_need(
        _row_bound(desc.t), round_up(desc.n, LANES), round_up(desc.k, LANES),
        a_isz=getattr(desc, "x_wire_itemsize", isz),
        b_isz=getattr(desc, "w_wire_itemsize", isz), out_isz=isz,
        acc=(_edge_bound(desc.t, 32), _edge_bound(desc.n, LANES)),
        row_scales=quant,
        col_rows=int(quant) + int(desc.epilogue in BIAS_EPILOGUES))
    return vmem_fits(need, machine.vmem_bytes)


def _predict_grouped_seconds(desc: GroupedGemmDescriptor, bm: int, bk: int,
                             bn: int, machine: MachineModel,
                             fused: bool = False) -> float:
    isz = jnp.dtype(desc.dtype).itemsize
    # Wire itemsizes / compute dtype (quant axis, DESIGN.md §13): backward
    # descriptors carry no quant spec and fall back to the wide dtype.
    x_sz = getattr(desc, "x_wire_itemsize", isz)
    w_sz = getattr(desc, "w_wire_itemsize", isz)
    compute_dt = getattr(desc, "compute_dtype", desc.dtype)
    gn = ceil_div(desc.n, bn)
    gk = ceil_div(desc.k, bk)
    if fused:
        # Ragged row blocks: each expert may add one partial block, plus
        # the zero-fill tail; no padded intermediate, no gather.
        gm = ceil_div(desc.t, bm) + desc.num_experts + 1
        stitch_s = 0.0
    else:
        # Pad/scatter lowering: padded rows still issue MACs, and the
        # scatter-in + gather-back copies are traffic the fused path
        # never generates.
        t_padded = round_up(desc.t, bm) + desc.num_experts * bm
        gm = ceil_div(t_padded, bm)
        stitch_bytes = 2 * desc.t * desc.k * isz          # scatter x
        stitch_bytes += (gm * bm + desc.t) * desc.n * isz  # gather out
        stitch_s = stitch_bytes / machine.hbm_bw
    steps = gm * gn * gk
    issued = 2 * gm * bm * gn * bn * desc.k
    compute_s = issued / machine.peak(compute_dt)
    traffic = (steps * (bm * bk * x_sz + bk * bn * w_sz)
               + gm * bm * desc.n * isz)
    memory_s = traffic / machine.hbm_bw
    return (max(compute_s, memory_s) + steps * machine.step_overhead_s
            + machine.launch_overhead_s + stitch_s)


def _grouped_legal(desc: GroupedGemmDescriptor,
                   machine: MachineModel) -> List[Tuple[int, int, int]]:
    """All VMEM-legal (bm, bk, bn) triples for one grouped descriptor."""
    sub, lane = machine.reg_tile(desc.dtype)
    isz = jnp.dtype(desc.dtype).itemsize
    legal = []
    for bm in _tile_candidates(desc.t, sub, lo=sub):
        for bn in _tile_candidates(desc.n, lane, lo=lane):
            for bk in _tile_candidates(desc.k, lane, lo=lane):
                vmem = bm * bn * 4 + 2 * (bm * bk + bk * bn) * isz
                if vmem > machine.vmem_bytes // 2:
                    continue
                legal.append((bm, bk, bn))
    if not legal:
        legal.append((sub, lane, lane))
    return legal


def plan_grouped(desc: GroupedGemmDescriptor,
                 machine: MachineModel = DEFAULT_MACHINE) -> GroupedGemmPlan:
    """Pick (bm, bk, bn): bm trades per-group padding against grid size.

    Like ``plan_gemm``, the analytical planner takes the paper's stance on
    dispatch: plans come out ``fused`` (single scheduled launch, no
    pad/scatter) whenever the staged operands fit VMEM
    (:func:`grouped_fused_legal`); the autotuner refines empirically.

    A mesh descriptor is planned per strategy (DESIGN.md §14): gathered
    (all-gather expert weights, full expert set over the local token
    shard) vs distributed (all_to_all tokens, local expert shard); the
    cheaper compute + communication total wins, recorded in ``comm``.
    """
    if desc.mesh is not None:
        cands = [dataclasses.replace(
                     plan_grouped(mesh_local_desc(desc, comm), machine),
                     desc=desc, comm=comm)
                 for comm in MESH_STRATEGIES]
        return min(cands, key=lambda p: p.predicted_seconds(machine))
    fused = grouped_fused_legal(desc, machine)
    best = min(_grouped_legal(desc, machine),
               key=lambda s: _predict_grouped_seconds(desc, *s,
                                                      machine=machine,
                                                      fused=fused))
    return GroupedGemmPlan(desc, *best, fused=fused)


@dataclasses.dataclass(frozen=True)
class TransposePlan:
    """Planned square tile edge ``bt`` of one (batched) blocked
    transpose."""

    desc: TransposeDescriptor
    bt: int
    plan_source: str = "model"  # see BlockingPlan.plan_source

    def predicted_seconds(self, machine: MachineModel = DEFAULT_MACHINE) -> float:
        return _predict_transpose_seconds(self.desc, self.bt, machine)


def _predict_transpose_seconds(desc: TransposeDescriptor, bt: int,
                               machine: MachineModel) -> float:
    # Batch is a grid dimension of the single launch (DESIGN.md §9).
    nb = max(1, desc.batch)
    steps = nb * ceil_div(desc.rows, bt) * ceil_div(desc.cols, bt)
    isz = jnp.dtype(desc.dtype).itemsize
    traffic = 2 * steps * bt * bt * isz  # read + mirrored write, padded
    return (traffic / machine.hbm_bw + steps * machine.step_overhead_s
            + machine.launch_overhead_s)


def _transpose_legal(desc: TransposeDescriptor,
                     machine: MachineModel) -> List[int]:
    """All VMEM-legal square tile edges for one transpose descriptor."""
    sub, lane = machine.reg_tile(desc.dtype)
    isz = jnp.dtype(desc.dtype).itemsize
    extent = max(desc.rows, desc.cols)
    legal = [bt for bt in _tile_candidates(extent, max(sub, 8), lo=32)
             if 2 * bt * bt * isz <= machine.vmem_bytes // 2]
    return legal or [lane]


def plan_transpose(desc: TransposeDescriptor,
                   machine: MachineModel = DEFAULT_MACHINE) -> TransposePlan:
    """Pick the square tile edge: biggest VMEM-legal tile wins on traffic,
    smaller tiles win on ragged edges (masked-write waste)."""
    best = min(_transpose_legal(desc, machine),
               key=lambda bt: _predict_transpose_seconds(desc, bt, machine))
    return TransposePlan(desc, best)


@dataclasses.dataclass(frozen=True)
class SsdChunkPlan:
    """The SSD ladder has no free tiling knobs — the whole (Q, n/p) cell
    lives in VMEM per grid step — but the uniform plan object carries the
    VMEM-fit verdict, the ``fused`` execution-path bit (scan form only)
    and the cost estimate for the engine's accounting."""

    desc: SsdChunkDescriptor
    fits_vmem: bool
    # Scan form (desc.chunks >= 1) only: execute the whole chunked scan —
    # intra-chunk ladder AND inter-chunk recurrence — in ONE pallas_call
    # with the (p, n) state carried as accumulator scratch (DESIGN.md §10)
    # instead of the diag kernel + XLA associative-scan stitch.
    fused: bool = False
    plan_source: str = "model"  # see BlockingPlan.plan_source

    def predicted_seconds(self, machine: MachineModel = DEFAULT_MACHINE) -> float:
        """Cost-model estimate: the non-fused scan pays the XLA
        inter-chunk stitch (per-chunk state tensors written and re-read
        around the associative scan) that the carried accumulator never
        materializes."""
        d = self.desc
        compute_s = d.flops / machine.peak(d.dtype)
        memory_s = (d.in_bytes + d.out_bytes) / machine.hbm_bw
        stitch_s = 0.0
        if d.chunks and not self.fused:
            # bx / s_incl / s_prev per (group, chunk), fp32, written by one
            # XLA op and read back by the next.
            stitch_bytes = 3 * d.groups * d.chunks * d.p * d.n * 4
            stitch_s = stitch_bytes / machine.hbm_bw
        return (max(compute_s, memory_s) + d.cells * machine.step_overhead_s
                + machine.launch_overhead_s + stitch_s)


def ssd_fused_legal(desc: SsdChunkDescriptor,
                    machine: MachineModel = DEFAULT_MACHINE) -> bool:
    """Can this SSD scan run as one carried-state ``pallas_call``?

    Only the scan form has a fused lowering; it needs one chunk's cell
    operands (double-buffered) plus the fp32 carried state and score
    scratch resident in VMEM."""
    if not desc.chunks:
        return False
    isz = jnp.dtype(desc.dtype).itemsize
    per_step = (2 * desc.q * desc.n + desc.q * desc.q
                + 2 * desc.q * desc.p + 2 * desc.q) * isz
    need = 2 * per_step                      # double-buffered chunk cell
    need += (desc.q * desc.q + 2 * desc.p * desc.n) * 4  # score + state
    return need <= machine.vmem_bytes // 2


def plan_ssd(desc: SsdChunkDescriptor,
             machine: MachineModel = DEFAULT_MACHINE) -> SsdChunkPlan:
    """Plan one SSD dispatch: record the VMEM-fit verdict and, for the
    scan form, take the paper's one-kernel stance whenever the carried-
    state lowering is legal (:func:`ssd_fused_legal`)."""
    isz = jnp.dtype(desc.dtype).itemsize
    per_step = (2 * desc.q * desc.n + desc.q * desc.q + 2 * desc.q * desc.p) * isz
    per_step += desc.q * desc.q * 4  # fp32 score scratch
    return SsdChunkPlan(desc, fits_vmem=per_step <= machine.vmem_bytes // 2,
                        fused=ssd_fused_legal(desc, machine))


# ---------------------------------------------------------------------------
# Backward-family planners (DESIGN.md §11)
# ---------------------------------------------------------------------------
# The backward walks reuse the forward plan classes (same tiling knobs,
# same tile schedules) under backward descriptors, so plans are cached /
# autotuned / provenance-counted exactly like forward plans.  The fused
# bit gates dispatch: when a backward lowering is not VMEM-legal the
# custom VJP falls back to reference-path autodiff and never reaches the
# engine.

def flash_bwd_fused_legal(desc: FlashBwdDescriptor,
                          machine: MachineModel = DEFAULT_MACHINE) -> bool:
    """Can this flash backward run as one scheduled ``pallas_call``?

    The backward walk stages one batch-head slice of q/k/v/o/do plus the
    dq/dk/dv outputs (fp32) and the staged LSE row
    (:func:`~repro.core.schedule.flash_bwd_vmem_need`, at the largest
    tiles and padded extents any plan can have)."""
    need = flash_bwd_vmem_need(
        _row_bound(desc.sq), _row_bound(desc.sk), desc.d,
        isz=jnp.dtype(desc.dtype).itemsize,
        bq=_edge_bound(desc.sq, 32), bk=_edge_bound(desc.sk, LANES))
    return vmem_fits(need, machine.vmem_bytes)


def plan_flash_bwd(desc: FlashBwdDescriptor,
                   machine: MachineModel = DEFAULT_MACHINE) -> FlashPlan:
    """Plan the flash backward walk: same (block_q, block_k) search as the
    forward — the backward reuses the forward ``FlashTileSchedule`` so the
    dKdV walk skips the same fully-masked causal k-blocks — gated by
    :func:`flash_bwd_fused_legal`."""
    fused = flash_bwd_fused_legal(desc, machine)
    best = min(_flash_legal(desc, machine),
               key=lambda s: _predict_flash_seconds(desc, *s, machine=machine,
                                                    fused=fused))
    return FlashPlan(desc, *best, fused=fused)


def grouped_bwd_fused_legal(desc: GroupedGemmBwdDescriptor,
                            machine: MachineModel = DEFAULT_MACHINE) -> bool:
    """Can this grouped-GEMM backward run as one scheduled ``pallas_call``?

    dgrad and wgrad share one launch: x, dy and dx stage whole, the expert
    panel double-buffers, and dW (plus db for biased epilogues) stages
    whole in fp32 for read-modify-write accumulation
    (:func:`~repro.core.schedule.grouped_bwd_vmem_need`, at the largest
    tiles and padded extents any plan can have)."""
    need = grouped_bwd_vmem_need(
        _row_bound(desc.t), round_up(desc.k, LANES), round_up(desc.n, LANES),
        experts=desc.num_experts, isz=jnp.dtype(desc.dtype).itemsize,
        acc=(_edge_bound(desc.t, 32), _edge_bound(desc.k, LANES)),
        with_db=desc.epilogue in BIAS_EPILOGUES)
    return vmem_fits(need, machine.vmem_bytes)


def plan_grouped_bwd(desc: GroupedGemmBwdDescriptor,
                     machine: MachineModel = DEFAULT_MACHINE
                     ) -> GroupedGemmPlan:
    """Plan the grouped backward: same (bm, bk, bn) search as the forward
    — both gradients walk ``GroupedTileSchedule`` runtime tile tables over
    ``group_sizes`` — gated by :func:`grouped_bwd_fused_legal`."""
    fused = grouped_bwd_fused_legal(desc, machine)
    best = min(_grouped_legal(desc, machine),
               key=lambda s: _predict_grouped_seconds(desc, *s,
                                                      machine=machine,
                                                      fused=fused))
    return GroupedGemmPlan(desc, *best, fused=fused)


def ssd_bwd_fused_legal(desc: SsdChunkBwdDescriptor,
                        machine: MachineModel = DEFAULT_MACHINE) -> bool:
    """Can this SSD-scan backward run as one carried-state ``pallas_call``?

    The reverse walk needs a chunk's forward cell, its dY cotangent and
    saved carried state (double-buffered), the cotangent output cell, and
    the fp32 dS carry + score scratch resident in VMEM."""
    if not desc.chunks:
        return False
    isz = jnp.dtype(desc.dtype).itemsize
    q, n, p = desc.q, desc.n, desc.p
    per_step = (2 * q * n + q * q + 2 * q * p + 2 * q) * isz  # fwd cell
    per_step += q * p * isz                                   # dY cell
    per_step += p * n * 4                                     # saved state
    per_step += (2 * q * n + q * q + q * p) * isz + 2 * q * 4  # cotangents
    need = 2 * per_step + (q * q + 2 * p * n) * 4 + p * n * 4
    return need <= machine.vmem_bytes // 2


def plan_ssd_bwd(desc: SsdChunkBwdDescriptor,
                 machine: MachineModel = DEFAULT_MACHINE) -> SsdChunkPlan:
    """Plan the SSD backward: no free tiling knobs — one reverse-walk
    launch carrying the (p, n) cotangent as accumulator scratch — gated by
    :func:`ssd_bwd_fused_legal`."""
    isz = jnp.dtype(desc.dtype).itemsize
    per_step = (2 * desc.q * desc.n + desc.q * desc.q
                + 2 * desc.q * desc.p) * isz
    per_step += desc.q * desc.q * 4
    return SsdChunkPlan(desc, fits_vmem=per_step <= machine.vmem_bytes // 2,
                        fused=ssd_bwd_fused_legal(desc, machine))


# ---------------------------------------------------------------------------
# Candidate enumeration (the autotuner's search space)
# ---------------------------------------------------------------------------

def candidate_plans(desc, machine: MachineModel = DEFAULT_MACHINE,
                    top_k: int = 8) -> List:
    """Top-``top_k`` machine-legal candidate plans for one descriptor.

    This is the empirical-search half of the measure→generate loop
    (DESIGN.md §7): the same legality constraints and
    ``max(compute, memory) + steps·overhead`` cost model that pick *the*
    plan analytically here rank *all* legal plans, and
    ``repro.core.autotune`` times the top K for real.  Candidates are
    deduplicated by their tiling knobs and sorted cheapest-first, so
    ``candidate_plans(desc, machine, 1)[0]`` always agrees with the
    family planner.
    """
    fam = desc.family
    cands: List = []
    seen = set()

    def add(plan, knob_key):
        if knob_key not in seen:
            seen.add(knob_key)
            cands.append(plan)

    if fam in ("gemm", "grouped_gemm") and desc.mesh is not None:
        # Mesh descriptor (DESIGN.md §14): the search space is the two
        # execution strategies, each carrying its own locally-planned
        # knobs — the autotuner times gathered vs distributed end to end
        # and the tuned cache records which won.
        planner = plan_gemm if fam == "gemm" else plan_grouped
        for comm in MESH_STRATEGIES:
            p = dataclasses.replace(planner(mesh_local_desc(desc, comm),
                                            machine),
                                    desc=desc, comm=comm)
            add(p, (comm,))
    elif fam == "gemm":
        # Fused (single-launch) and multi-launch lowerings of one region
        # cover are distinct candidates: the autotuner times both and the
        # tuned cache records which won (DESIGN.md §8).
        fused_ok = fused_legal(desc, machine)
        for shape in palette(ACC_BUDGET_ELEMS, machine, desc.in_dtype):
            for het in (True, False):
                p = plan_gemm(desc, machine, heterogeneous=het,
                              force_block=shape)
                for fused in ((True, False) if fused_ok else (False,)):
                    q = dataclasses.replace(p, fused=fused)
                    add(q, (q.regions, q.bk, fused))
    elif fam == "flash_attention":
        # Fused (scheduled single-launch) and dense-grid lowerings of one
        # tiling are distinct candidates, exactly as for dense GEMM.
        fused_ok = flash_fused_legal(desc, machine)
        for bq, bk in _flash_legal(desc, machine):
            for fused in ((True, False) if fused_ok else (False,)):
                add(FlashPlan(desc, bq, bk, fused=fused), (bq, bk, fused))
    elif fam == "grouped_gemm":
        # Fused (scheduled single-launch) and pad/scatter lowerings of one
        # tiling are distinct candidates, exactly as for dense GEMM.
        fused_ok = grouped_fused_legal(desc, machine)
        for bm, bk, bn in _grouped_legal(desc, machine):
            for fused in ((True, False) if fused_ok else (False,)):
                add(GroupedGemmPlan(desc, bm, bk, bn, fused=fused),
                    (bm, bk, bn, fused))
    elif fam == "flash_attention_bwd":
        # The backward walk has a single (fused) lowering — the non-fused
        # alternative is reference-path autodiff outside the engine — so
        # only fused variants enter the search when legal.
        fused_ok = flash_bwd_fused_legal(desc, machine)
        for bq, bk in _flash_legal(desc, machine):
            add(FlashPlan(desc, bq, bk, fused=fused_ok), (bq, bk))
    elif fam == "grouped_gemm_bwd":
        # As for flash backward: fused-or-fallback, no pad/scatter variant.
        fused_ok = grouped_bwd_fused_legal(desc, machine)
        for bm, bk, bn in _grouped_legal(desc, machine):
            add(GroupedGemmPlan(desc, bm, bk, bn, fused=fused_ok),
                (bm, bk, bn))
    elif fam == "ssd_chunk_bwd":
        # No free tiling knobs and a single reverse-walk lowering.
        add(plan_ssd_bwd(desc, machine), ())
    elif fam == "flash_decode":
        # No free knobs: the page size is the k-block (fixed at cache
        # construction) and the walk is always the scheduled single launch.
        add(plan_flash_decode(desc, machine), ())
    elif fam == "transpose":
        for bt in _transpose_legal(desc, machine):
            add(TransposePlan(desc, bt), (bt,))
    elif fam == "ssd_chunk":
        # No free tiling knobs; the scan form still has two lowerings
        # (carried-state fused vs diag kernel + XLA scan) to choose from.
        p = plan_ssd(desc, machine)
        if ssd_fused_legal(desc, machine):
            for fused in (True, False):
                q = dataclasses.replace(p, fused=fused)
                add(q, (fused,))
        else:
            add(dataclasses.replace(p, fused=False), ())
    else:
        raise KeyError(f"no candidate enumerator for family {fam!r}")

    cands.sort(key=lambda p: p.predicted_seconds(machine))
    return cands[:max(1, top_k)]
