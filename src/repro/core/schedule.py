"""The schedule layer — family-generic fused-execution machinery (DESIGN.md §9).

The paper's generator emits ONE kernel per problem: main tiles and edge
tiles are covered inside it by predication and a two-step load/store path,
so raggedness never costs extra dispatches or operand copies (§IV, Fig 7).
PR 3 built that machinery for dense GEMM only, inlined into
``core/blocking.py`` + ``kernels/gemm/kernel.py``.  This module hoists it
into a family-generic subsystem so every ragged family can flatten its
work-list into tile tables walked by a single ``pallas_call``:

  * :class:`TileSchedule` — the trace-time flattening of a dense region
    cover (GEMM): per-tile ownership rectangles + clamped window origins;
  * :class:`GroupedTileSchedule` — the *runtime* flattening of a ragged
    expert row partition (grouped GEMM / MoE): the geometry is static,
    the tables are data, computed from ``group_sizes`` with jnp ops and
    shipped to the kernel as a scalar-prefetch operand;
  * :class:`FlashTileSchedule` — the trace-time flattening of the flash
    attention (q-block, k-block) walk (DESIGN.md §10): fully-masked
    causal k-blocks are dropped at plan time instead of skipped at run
    time, and the online-softmax carry (m/l/acc) threads through the
    flat tile walk as accumulator state;
  * scalar-prefetch table packing (``pack_table`` — int32, the SMEM
    currency);
  * in-kernel predication helpers shared by every fused kernel body:
    clamped K windows + tail masks (the predicate-register analogue) and
    ownership-masked read-modify-write stores (the two-step store path);
  * launch accounting (:func:`plan_launches`) — the per-plan
    ``pallas_call`` count that executors report via
    ``engine.count_launches`` and cost models charge at
    ``machine.launch_overhead_s``.

``repro.core.blocking`` builds schedules from plans; ``repro.kernels.*``
consume them.  This module imports neither — it is the seam between the
planning layer and the generated kernels.
"""
from __future__ import annotations

import dataclasses
import math
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def ceil_div(a: int, b: int) -> int:
    """Ceiling division on Python ints: ``ceil(a / b)`` without floats."""
    return -(-a // b)


# Channel-block width of the ``per_tile`` quantization scheme (DESIGN.md
# §13): scale tables hold one f32 scale per QUANT_TILE-wide block of the
# channel axis, and each tile row's ``scale_idx`` column names the block
# its clamped window origin falls in.  Pinned to the lane count (128) so a
# scale block never straddles a native register tile.
QUANT_TILE = 128


def round_up(a: int, b: int) -> int:
    """Round ``a`` up to the nearest multiple of ``b``."""
    return ceil_div(a, b) * b


# Lane width of a TPU vector register: a window origin on an operand's
# minor dimension must be a multiple of it for Mosaic to lower the load.
LANES = 128


def sublanes(dtype) -> int:
    """Rows of one native register tile for ``dtype`` (8 for 32-bit, 16
    for 16-bit, 32 for 8-bit values): the alignment Mosaic needs to prove
    for a window origin on an operand's second-minor dimension."""
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


def padded_extent(extent: int, window: int, align: int) -> int:
    """Extent of the staged buffer a fixed ``window`` slides over.

    One window spanning the whole extent sits at origin 0.  Otherwise the
    buffer is rounded up to ``align`` so that a clamped edge window
    (origin ``extent_p - window``) stays aligned: its overhang reads the
    block padding past the operand, which the ownership and K-tail masks
    discard, and the writeback never stores it.
    """
    return extent if window >= extent else round_up(extent, align)


# Scoped VMEM of the fused kernels, which stage whole operands.  Mosaic's
# default limit (16 MiB on v5e) is below what they need, so each asks for
# its own (:func:`vmem_limit`), never more than the cap, which leaves room
# under the chip's 128 MiB for Mosaic's internal scratch.  The planners'
# legality checks count the same bytes (:func:`vmem_fits`), so a plan
# they call fused is one Mosaic accepts.
VMEM_LIMIT_CAP = 100 << 20
VMEM_LIMIT_FLOOR = 16 << 20
# Room for the values a kernel body keeps in VMEM beside its declared
# blocks and scratch (score tiles, masks, casts).
VMEM_HEADROOM = 4 << 20


def tile_bytes(rows: int, cols: int, itemsize: int) -> int:
    """VMEM bytes of a ``(rows, cols)`` block: it is laid out in whole
    native register tiles (:func:`sublanes` x :data:`LANES`), so a
    ``(t, 1)`` column of scales takes the room of a ``(t, 128)`` panel."""
    sub = 8 * max(1, 4 // itemsize)
    return round_up(rows, sub) * round_up(cols, LANES) * itemsize


def vmem_need(blocks, scratch: int = 0) -> int:
    """Scoped VMEM of a kernel that stages ``blocks`` — ``(rows, cols,
    itemsize)`` each, double-buffered by the pipeline, inputs and outputs
    alike — next to ``scratch`` bytes of scratch buffers."""
    return (2 * sum(tile_bytes(*b) for b in blocks) + scratch
            + VMEM_HEADROOM)


def vmem_fits(need: int, capacity: int) -> bool:
    """Can a kernel needing ``need`` bytes (:func:`vmem_need`) run on a
    chip with ``capacity`` bytes of VMEM?"""
    return need <= min(capacity, VMEM_LIMIT_CAP)


def vmem_limit(need: int) -> int:
    """The ``vmem_limit_bytes`` a kernel needing ``need`` bytes asks for."""
    return int(min(max(need, VMEM_LIMIT_FLOOR), VMEM_LIMIT_CAP))


def matmul_vmem_need(rows: int, n: int, k: int, *, a_isz: int, b_isz: int,
                     out_isz: int, acc: Tuple[int, int], layout: str = "nn",
                     accumulate: bool = False, row_scales: bool = False,
                     col_rows: int = 0) -> int:
    """Scoped VMEM of a fused (grouped) GEMM over staged extents ``rows``
    x ``n`` x ``k``: A (or x) and the output whole, one B (or expert
    weight) panel, a C input when ``accumulate``, an ``(rows, 1)`` f32
    column of row scales, ``col_rows`` ``(1, n)`` rows (column scales,
    bias; 32 bytes a lane whatever their dtype) and the ``acc`` f32 /
    int32 accumulator.  The kernel builders pass their exact extents; the
    legality checks pass upper bounds, so legal implies it fits."""
    blocks = [(rows, k, a_isz),
              (k, n, b_isz) if layout == "nn" else (n, k, b_isz),
              (rows, n, out_isz)]
    if accumulate:
        blocks.append((rows, n, out_isz))
    if row_scales:
        blocks.append((rows, 1, 4))
    blocks += [(1, n, 4)] * col_rows
    return vmem_need(blocks, tile_bytes(*acc, 4))


def flash_vmem_need(sq: int, sk: int, d: int, *, isz: int, bq: int, bk: int,
                    lse: bool = False) -> int:
    """Scoped VMEM of the fused flash forward over staged extents ``sq`` /
    ``sk``: q, out, k and v whole (plus the ``(sq, 1)`` LSE column when
    drained), the running max / denominator / accumulator and the
    ``(bq, bk)`` f32 score tile."""
    blocks = [(sq, d, isz)] * 2 + [(sk, d, isz)] * 2
    if lse:
        blocks.append((sq, 1, 4))
    scratch = (2 * tile_bytes(bq, 1, 4) + tile_bytes(bq, d, 4)
               + tile_bytes(bq, bk, 4))
    return vmem_need(blocks, scratch)


def flash_bwd_vmem_need(sq: int, sk: int, d: int, *, isz: int, bq: int,
                        bk: int) -> int:
    """Scoped VMEM of the fused flash backward: q, o, dO and the LSE
    column, k and v whole, dQ / dK / dV whole in f32, the D row and dQ
    accumulator, and the ``(bq, bk)`` f32 score tile."""
    blocks = ([(sq, d, isz)] * 3 + [(sk, d, isz)] * 2 + [(sq, 1, 4)]
              + [(sq, d, 4)] + [(sk, d, 4)] * 2)
    scratch = (tile_bytes(bq, 1, 4) + tile_bytes(bq, d, 4)
               + tile_bytes(bq, bk, 4))
    return vmem_need(blocks, scratch)


def grouped_bwd_vmem_need(t: int, k: int, n: int, *, experts: int, isz: int,
                          acc: Tuple[int, int], with_db: bool = False) -> int:
    """Scoped VMEM of the fused grouped backward: x, dy, one expert panel,
    dX (f32) and every expert's dW (f32, plus db) whole, and the ``acc``
    f32 accumulator."""
    blocks = ([(t, k, isz), (t, n, isz), (k, n, isz), (t, k, 4)]
              + [(k, n, 4)] * experts)
    if with_db:
        blocks.append((experts, n, 4))
    return vmem_need(blocks, tile_bytes(*acc, 4))


def proven_align(origins, cap: int = LANES) -> int:
    """Largest power of two ``<= cap`` dividing every origin: the value a
    kernel may pass to ``pl.multiple_of`` for a table-driven window origin
    (a hint that is false would be undefined behaviour on the chip)."""
    g = 0
    for o in origins:
        g = math.gcd(g, int(o))
    a = cap
    while g % a:
        a //= 2
    return a


# ---------------------------------------------------------------------------
# Dense (GEMM) tile schedules — trace-time tables
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TileSchedule:
    """Flattened tile schedule of one dense region cover (DESIGN.md §9).

    The fused single-launch GEMM kernel walks this instead of launching one
    ``pallas_call`` per region: every region's grid is unrolled into a flat
    tuple of tiles, all trace-time constants, which the kernel receives as
    a scalar-prefetch table and indexes by ``pl.program_id``.

    ``blocks`` are the distinct effective block geometries (region blocks
    clamped to the matrix so a clamped load window always fits the operand
    buffers); each tile row is

        (row0, col0, row_end, col_end, row_start, col_start, block_id,
         scale_idx)

    where ``[row0, row_end) x [col0, col_end)`` is the set of C elements
    the tile owns (the predicate mask) and ``(row_start, col_start)`` is
    the clamped origin of its fixed-shape load/store window — the paper's
    two-step load/store path: edge windows slide inward and the mask keeps
    each element owned by exactly one tile.  ``scale_idx`` is the quant
    axis's scale-table coordinate (DESIGN.md §13): the ``per_tile`` scale
    block (:data:`QUANT_TILE`-wide) the window origin's row falls in —
    carried on every tile so quantized and wide plans share one table
    layout; wide kernels simply never read the column.

    ``m_p``/``n_p``/``k_p`` are the staged buffer extents
    (:func:`padded_extent`): windows slide over them, so clamped edge
    origins stay aligned, and ``row_align``/``col_align``/``k_align`` are
    the alignments every row / column / K-panel origin provably has
    (:func:`proven_align`) — the hints the kernel hands Mosaic.
    """

    m: int
    n: int
    k: int
    bk: int
    k_steps: int
    blocks: Tuple[Tuple[int, int], ...]
    tiles: Tuple[Tuple[int, int, int, int, int, int, int, int], ...]
    m_p: int
    n_p: int
    k_p: int
    row_align: int
    col_align: int
    k_align: int

    @property
    def num_tiles(self) -> int:
        return len(self.tiles)

    def k_window(self, ks):
        """``(k0, kstart)`` of K-panel ``ks`` over the staged K extent."""
        return clamped_k_window(ks, self.bk, self.k_p)

    def validate(self):
        """Every C element owned by exactly one tile mask."""
        owned = 0
        for row0, col0, row_end, col_end, rs, cs, bid, sidx in self.tiles:
            bm_e, bn_e = self.blocks[bid]
            assert 0 <= rs and rs + bm_e <= self.m_p, (rs, bm_e, self.m_p)
            assert 0 <= cs and cs + bn_e <= self.n_p, (cs, bn_e, self.n_p)
            assert rs <= row0 and row_end <= rs + bm_e
            assert cs <= col0 and col_end <= cs + bn_e
            assert row_end <= self.m and col_end <= self.n
            assert rs % self.row_align == 0 and cs % self.col_align == 0
            assert sidx == rs // QUANT_TILE, (sidx, rs)
            owned += (row_end - row0) * (col_end - col0)
        assert owned == self.m * self.n, (owned, self.m * self.n)
        return True


def flatten_regions(m: int, n: int, k: int, bk: int, regions: Sequence,
                    row_align: int = 8) -> TileSchedule:
    """Flatten a region cover into the fused kernel's tile tables.

    ``regions`` is any sequence of objects with ``row0/col0/rows/cols``
    ownership rectangles and ``bm/bn`` block geometry (the
    :class:`repro.core.blocking.Region` shape).  Region blocks are clamped
    to the staged buffer (``bm_e = min(bm, m_p)``) so every fixed-shape
    window fits it; a clamped block walks its region with the *effective*
    stride, so raggedness is absorbed by the per-tile ownership mask,
    never by the shapes.  ``row_align`` is the operand dtype's sublane
    count (:func:`sublanes`): rows are staged in whole register tiles,
    even under one window, because Mosaic cannot mask a packed (16-bit
    or narrower) tile of fewer rows than one sublane group.
    """
    bk = max(1, min(bk, k))
    m_p = round_up(m, row_align)
    n_p = n if all(r.bn >= n for r in regions) else round_up(n, LANES)
    k_p = padded_extent(k, bk, LANES)
    blocks: List[Tuple[int, int]] = []
    ids = {}
    tiles = []
    for r in regions:
        bm_e, bn_e = min(r.bm, m_p), min(r.bn, n_p)
        bid = ids.get((bm_e, bn_e))
        if bid is None:
            bid = ids[(bm_e, bn_e)] = len(blocks)
            blocks.append((bm_e, bn_e))
        for i in range(ceil_div(r.rows, bm_e)):
            row0 = r.row0 + i * bm_e
            row_end = min(row0 + bm_e, r.row0 + r.rows)
            for j in range(ceil_div(r.cols, bn_e)):
                col0 = r.col0 + j * bn_e
                col_end = min(col0 + bn_e, r.col0 + r.cols)
                rs = min(row0, m_p - bm_e)
                tiles.append((row0, col0, row_end, col_end,
                              rs, min(col0, n_p - bn_e),
                              bid, rs // QUANT_TILE))
    k_steps = ceil_div(k, bk)
    return TileSchedule(
        m=m, n=n, k=k, bk=bk, k_steps=k_steps, blocks=tuple(blocks),
        tiles=tuple(tiles), m_p=m_p, n_p=n_p, k_p=k_p,
        row_align=proven_align(t[4] for t in tiles),
        col_align=proven_align(t[5] for t in tiles),
        k_align=proven_align(min(s * bk, k_p - bk) for s in range(k_steps)))


def pack_table(rows: Sequence[Sequence[int]]) -> np.ndarray:
    """Pack tile rows into the int32 scalar-prefetch table the kernels ride.

    numpy, not jnp: trace-time tables are baked into the kernel closure,
    and a traced constant must not leak into the kernel cache (runtime
    tables — :meth:`GroupedTileSchedule.tables` — are jnp by construction
    and travel as operands instead).
    """
    table = np.asarray(rows, dtype=np.int32)
    assert table.ndim == 2, table.shape
    return table


# ---------------------------------------------------------------------------
# Ragged (grouped) tile schedules — runtime tables, static geometry
# ---------------------------------------------------------------------------

# Tile states in the grouped table's ``state`` column.
TILE_SKIP = 0     # beyond the active tile count: no work
TILE_COMPUTE = 1  # owns rows of one expert: accumulate + store
TILE_ZERO = 2     # owns rows past sum(group_sizes): store zeros


@dataclasses.dataclass(frozen=True)
class GroupedTileSchedule:
    """Schedule of a ragged row partition (grouped GEMM, DESIGN.md §9).

    The *geometry* is trace-time (effective blocks, grid extents, the
    static ``max_tiles`` bound) but the *tables* are runtime data: the
    router decides ``group_sizes`` per call, so each expert's row blocks
    are computed with jnp ops (:meth:`tables`) and ride to the kernel as
    a scalar-prefetch operand — no host-side pad/scatter, no padded
    intermediate, no gather-back.

    Each table row is ``(row0, row_end, row_start, expert, state)``:
    ``[row0, row_end)`` are the x/out rows the tile owns, ``row_start``
    is the clamped origin of its fixed ``bm``-row window, ``expert``
    selects the weight (and bias) panel, and ``state`` marks the tile as
    compute / zero-fill (rows past ``sum(group_sizes)``) / skip.

    With ``row_align > 1`` each group's blocks start at its offset
    rounded down to ``row_align`` (the first block owns fewer rows), so
    every window origin is aligned for Mosaic; windows then slide over the
    staged extents ``t_p``/``n_p``/``k_p`` (:func:`padded_extent`).
    """

    t: int
    k: int
    n: int
    num_experts: int
    bm: int
    bk: int
    bn: int
    row_align: int = 1

    def __post_init__(self):
        assert self.bm <= self.t and self.bn <= self.n and self.bk <= self.k

    @property
    def t_p(self) -> int:
        return padded_extent(self.t, self.bm, self.row_align)

    @property
    def n_p(self) -> int:
        return padded_extent(self.n, self.bn, LANES)

    @property
    def k_p(self) -> int:
        return padded_extent(self.k, self.bk, LANES)

    @property
    def row_origin_align(self) -> int:
        """Alignment every table ``row_start`` provably has."""
        if self.bm >= self.t:
            return LANES  # one window: every origin is 0
        return self.row_align if self.bm % self.row_align == 0 else 1

    @property
    def col_align(self) -> int:
        return proven_align(min(j * self.bn, self.n_p - self.bn)
                            for j in range(self.n_steps))

    @property
    def k_align(self) -> int:
        return proven_align(min(s * self.bk, self.k_p - self.bk)
                            for s in range(self.k_steps))

    @property
    def max_tiles(self) -> int:
        """Static row-tile bound: every expert may add one partial block
        (and one more for its rounded-down start), plus the zero-fill
        tail region."""
        slack = (self.num_experts + 1) * (self.row_align - 1)
        return ceil_div(self.t + slack, self.bm) + self.num_experts + 1

    @property
    def k_steps(self) -> int:
        return ceil_div(self.k, self.bk)

    @property
    def n_steps(self) -> int:
        return ceil_div(self.n, self.bn)

    def tables(self, group_sizes: jax.Array) -> jax.Array:
        """Runtime tile table: ``(max_tiles, 5)`` int32 from the router's
        ``group_sizes``.  All shapes static, values dynamic — traceable
        under ``jit``.  Rows past ``sum(group_sizes)`` form a zero-fill
        pseudo-group so the kernel covers every output row exactly once.
        """
        bm, t, e = self.bm, self.t, self.num_experts
        sizes = group_sizes.astype(jnp.int32)
        tail = t - jnp.sum(sizes)
        all_sizes = jnp.concatenate([sizes, tail[None]])          # (E+1,)
        all_off = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                   jnp.cumsum(all_sizes)])        # (E+2,)
        # Each group's block grid starts at its offset rounded down to
        # row_align; empty groups get no blocks.
        lead = all_off[:-1] % self.row_align                      # (E+1,)
        nblocks = jnp.where(all_sizes > 0,
                            (all_sizes + lead + bm - 1) // bm, 0)  # (E+1,)
        bstart = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                  jnp.cumsum(nblocks)])           # (E+2,)
        g = jnp.arange(self.max_tiles, dtype=jnp.int32)
        # Which (pseudo-)group owns tile g; empty groups contribute no
        # tiles (their bstart span is empty, searchsorted skips them).
        owner = jnp.clip(
            jnp.searchsorted(bstart, g, side="right") - 1, 0, e)
        local = g - bstart[owner]
        start = all_off[owner] - lead[owner] + local * bm
        row0 = jnp.maximum(start, all_off[owner])
        row_end = jnp.minimum(start + bm, all_off[owner] + all_sizes[owner])
        active = g < bstart[-1]
        row0 = jnp.where(active, row0, t)
        row_end = jnp.where(active, row_end, t)
        start = jnp.where(active, start, t)
        rs = jnp.clip(jnp.minimum(start, self.t_p - bm), 0)
        expert = jnp.minimum(owner, e - 1)  # always a legal panel index
        state = jnp.where(
            active & (row_end > row0),
            jnp.where(owner < e, TILE_COMPUTE, TILE_ZERO), TILE_SKIP)
        return jnp.stack([row0, row_end, rs, expert, state],
                         axis=1).astype(jnp.int32)

    def validate_tables(self, table, group_sizes) -> bool:
        """Property check on one concrete table (tests): every output row
        owned by exactly one tile, windows in bounds, experts consistent.
        """
        table = np.asarray(table)
        sizes = np.asarray(group_sizes, dtype=np.int64)
        offsets = np.concatenate([[0], np.cumsum(sizes)])
        assert table.shape == (self.max_tiles, 5), table.shape
        assert table.dtype == np.int32, table.dtype
        owner_of = np.full(self.t, -1, dtype=np.int64)
        for row0, row_end, rs, expert, state in table:
            if state == TILE_SKIP:
                assert row0 == row_end, (row0, row_end)
                continue
            assert 0 <= rs and rs + self.bm <= self.t_p, (rs, self.bm)
            assert rs % self.row_origin_align == 0, rs
            assert rs <= row0 and row_end <= rs + self.bm
            assert 0 <= expert < self.num_experts
            assert (owner_of[row0:row_end] == -1).all(), "row owned twice"
            owner_of[row0:row_end] = expert if state == TILE_COMPUTE else -2
            if state == TILE_COMPUTE:
                # owned rows really belong to that expert
                assert offsets[expert] <= row0
                assert row_end <= offsets[expert + 1]
            else:  # TILE_ZERO: rows past the ragged total
                assert row0 >= offsets[-1]
        assert (owner_of != -1).all(), "uncovered output rows"
        return True


# ---------------------------------------------------------------------------
# Paged decode tile schedules — runtime tables over live KV pages
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DecodeTileSchedule:
    """Schedule of one continuous-batching decode step over a paged KV
    cache (DESIGN.md §12).

    The serving runtime stores each sequence's KV in fixed-size *pages*
    of a shared pool, mapped by per-sequence block tables
    (``runtime/pages.py``).  A decode step attends each sequence's single
    query row against exactly its live pages — a ragged walk whose
    raggedness is *runtime data* (sequence lengths change every step, the
    batch churns with admissions/evictions), so it gets the
    :class:`GroupedTileSchedule` treatment, not the trace-time
    :class:`FlashTileSchedule` one: the geometry (pool size, page size,
    slot count, the static ``max_tiles`` bound) is trace-time, the tables
    are jnp data computed from ``(block_tables, lengths)`` each step and
    shipped to the kernel as a scalar-prefetch operand.  Batch churn
    never retraces — the kernel is shape-specialized, the batch
    composition is data.

    Each table row is ``(seq, page, k_len, first, last)``: the decode
    kernel's grid step ``t`` attends query row ``seq`` against pool page
    ``page``, of which the first ``k_len`` slots are live (the tail
    predicate), with ``first``/``last`` bracketing the sequence's
    contiguous page walk for the online-softmax carry exactly as in the
    flash schedule.  A sequence always owns at least one table row — an
    empty (length-0 / inactive) slot gets a single fully-masked row so
    its carry still initializes and drains (to zeros) without branching.
    """

    num_seqs: int    # decode slots (pool block-table rows)
    pages: int       # pool size in pages
    page_size: int   # KV slots per page
    max_blocks: int  # block-table width: max pages one sequence may own

    def __post_init__(self):
        assert self.num_seqs > 0 and self.pages > 0
        assert self.page_size > 0 and self.max_blocks > 0

    @property
    def max_tiles(self) -> int:
        """Static tile bound: live pages are exclusively owned so at most
        ``pages`` compute tiles exist pool-wide (never more than
        ``num_seqs * max_blocks``), plus one dummy tile per sequence for
        the ≥1-row floor."""
        return min(self.num_seqs * self.max_blocks, self.pages) \
            + self.num_seqs

    @property
    def max_len(self) -> int:
        """Longest sequence the block tables can map."""
        return self.max_blocks * self.page_size

    def tables(self, block_tables: jax.Array,
               lengths: jax.Array) -> jax.Array:
        """Runtime tile table: ``(max_tiles, 5)`` int32 from this step's
        ``block_tables`` (num_seqs, max_blocks) and ``lengths``
        (num_seqs,).  All shapes static, values dynamic — traceable under
        ``jit``, so admissions/evictions/growth never recompile."""
        P, S = self.page_size, self.num_seqs
        lengths = lengths.astype(jnp.int32)
        # ceil(len/P) live pages per sequence, floored at one (dummy) tile
        # so every slot's carry initializes and drains.
        nblocks = jnp.maximum((lengths + P - 1) // P, 1)       # (S,)
        bstart = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                  jnp.cumsum(nblocks)])        # (S+1,)
        g = jnp.arange(self.max_tiles, dtype=jnp.int32)
        seq = jnp.clip(jnp.searchsorted(bstart, g, side="right") - 1,
                       0, S - 1)
        local = g - bstart[seq]
        active = g < bstart[-1]
        lcl = jnp.clip(local, 0, self.max_blocks - 1)
        page = jnp.clip(block_tables[seq, lcl], 0, self.pages - 1)
        k_len = jnp.clip(lengths[seq] - local * P, 0, P)
        first = active & (local == 0)
        last = active & (local == nblocks[seq] - 1)
        page = jnp.where(active, page, 0)
        k_len = jnp.where(active, k_len, 0)
        return jnp.stack([seq, page, k_len,
                          first.astype(jnp.int32), last.astype(jnp.int32)],
                         axis=1).astype(jnp.int32)

    def validate_tables(self, table, block_tables, lengths) -> bool:
        """Property check on one concrete table (tests): every sequence's
        live pages visited exactly once, in block-table order, with
        correct tail lengths and carry flags; inactive tail rows inert."""
        table = np.asarray(table)
        bt = np.asarray(block_tables)
        lengths = np.asarray(lengths, dtype=np.int64)
        P = self.page_size
        assert table.shape == (self.max_tiles, 5), table.shape
        assert table.dtype == np.int32, table.dtype
        nblocks = np.maximum(-(-lengths // P), 1)
        total = int(nblocks.sum())
        assert total <= self.max_tiles, (total, self.max_tiles)
        visited = {}  # seq -> list of (page, k_len)
        open_seq = None
        for i, (seq, page, k_len, first, last) in enumerate(table):
            if i >= total:  # inactive tail: inert rows, legal indices only
                assert first == 0 and last == 0 and k_len == 0, table[i]
                assert 0 <= seq < self.num_seqs and 0 <= page < self.pages
                continue
            assert 0 <= seq < self.num_seqs and 0 <= page < self.pages
            if first:
                assert open_seq is None, "carry re-opened before drain"
                open_seq = seq
                visited.setdefault(int(seq), [])
            assert open_seq == seq, "row outside the open carry"
            visited[int(seq)].append((int(page), int(k_len)))
            if last:
                open_seq = None
        assert open_seq is None, "carry never drained"
        for s in range(self.num_seqs):
            walk = visited.get(s, [])
            n, length = int(nblocks[s]), int(lengths[s])
            assert len(walk) == n, (s, walk, n)
            # pages follow the block table; each live page exactly once
            pages_seen = [p for p, _ in walk]
            if length > 0:
                expect = [int(bt[s, j]) for j in range(n)]
                assert pages_seen == expect, (s, pages_seen, expect)
                assert len(set(pages_seen)) == n, "page visited twice"
            # k_len: P per full page, the ragged tail on the last one
            assert sum(kl for _, kl in walk) == length, (s, walk, length)
            for j, (_, kl) in enumerate(walk):
                want = min(max(length - j * P, 0), P)
                assert kl == want, (s, j, kl, want)
        return True


# ---------------------------------------------------------------------------
# Flash-attention tile schedules — trace-time tables, causal-aware
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class FlashTileSchedule:
    """Flattened (q-block, k-block) walk of one flash attention problem
    (DESIGN.md §10).

    The causal mask is a *cover* problem, not a runtime branch: a k-block
    strictly above a q-block's diagonal contributes nothing, so it is
    dropped when the tile table is built — at long causal sequences
    roughly half the dense (q, k) grid never reaches the kernel.  The
    surviving tiles are ordered q-block-major with each q-block's
    k-blocks contiguous and ascending, so the online-softmax carry
    (running max / denominator / output accumulator) threads through the
    flat walk as VMEM accumulator state, reset at ``first`` and drained
    at ``last``.

    Each tile row is ``(q0, q_end, qs, k0, k_end, ks, first, last)``:
    ``[q0, q_end)`` are the query rows the tile's q-block *owns*, ``qs``
    / ``ks`` are the clamped origins of the fixed ``(bq, d)`` / ``(bk,
    d)`` windows (the two-step load path: ragged edge windows slide
    inward instead of shrinking), ``[k0, k_end)`` are the key columns
    this tile contributes (the predicate on the clamped-window overlap
    and the sk tail), and ``first``/``last`` flag the q-block's carry
    boundaries.  Windows slide over the staged extents ``sq_p``/``sk_p``
    (:func:`padded_extent`), so every origin is a multiple of ``q_align``
    / ``k_align`` (:func:`proven_align`).
    """

    sq: int
    sk: int
    bq: int
    bk: int
    causal: bool
    tiles: Tuple[Tuple[int, int, int, int, int, int, int, int], ...]
    sq_p: int
    sk_p: int
    q_align: int
    k_align: int

    @property
    def num_tiles(self) -> int:
        """Tiles actually walked (per batch x head slice)."""
        return len(self.tiles)

    @property
    def dense_tiles(self) -> int:
        """Tile count of the dense (q, k) grid the causal drop beats."""
        return ceil_div(self.sq, self.bq) * ceil_div(self.sk, self.bk)

    def validate(self):
        """Every query row drained exactly once; every kept tile's k
        range in bounds, non-empty and causal-reachable; carry flags
        bracket each q-block's contiguous k walk."""
        drained = np.zeros(self.sq, dtype=np.int64)
        open_q = None  # ownership of the q-block currently being walked
        prev_k_end = 0
        for q0, q_end, qs, k0, k_end, ks, first, last in self.tiles:
            assert 0 <= qs and qs + self.bq <= self.sq_p, (qs, self.bq)
            assert 0 <= ks and ks + self.bk <= self.sk_p, (ks, self.bk)
            assert qs % self.q_align == 0 and ks % self.k_align == 0
            assert qs <= q0 and q_end <= qs + self.bq
            assert ks <= k0 and k_end <= ks + self.bk
            assert k0 < k_end <= self.sk
            if self.causal:
                # at least one owned (q, k) pair is visible
                assert k0 <= q_end - 1, (k0, q_end)
            if first:
                assert open_q is None, "carry re-opened before drain"
                open_q, prev_k_end = (q0, q_end), 0
            assert open_q == (q0, q_end), "tile outside the open carry"
            assert k0 == prev_k_end, "k walk not contiguous ascending"
            prev_k_end = k_end
            if last:
                drained[q0:q_end] += 1
                open_q = None
        assert open_q is None, "carry never drained"
        assert (drained == 1).all(), "query rows not drained exactly once"
        if self.causal and self.sq == self.sk and self.sq > self.bq + self.bk:
            assert self.num_tiles < self.dense_tiles
        return True


def flash_tile_schedule(sq: int, sk: int, bq: int, bk: int,
                        causal: bool, row_align: int = 8) -> FlashTileSchedule:
    """Build the flattened causal-aware (q, k) tile walk.

    Block edges are clamped to the problem so every fixed-shape window
    fits the staged operands (padded to ``row_align`` rows when a window
    does not span them); for ``causal=True`` a k-block whose first column
    ``k0`` exceeds the q-block's last *owned* row is fully masked and
    never enters the table (the heterogeneous-cover idea applied to the
    causal triangle — at plan time, not as a run-time branch).
    """
    bq = max(1, min(bq, sq))
    bk = max(1, min(bk, sk))
    sq_p = padded_extent(sq, bq, row_align)
    sk_p = padded_extent(sk, bk, row_align)
    ck = ceil_div(sk, bk)
    tiles: List[Tuple[int, ...]] = []
    for qi in range(ceil_div(sq, bq)):
        q0 = qi * bq
        q_end = min(q0 + bq, sq)
        qs = min(q0, sq_p - bq)
        # k-blocks with any visible column for the owned rows [q0, q_end)
        k_hi = min(ck, ceil_div(q_end, bk)) if causal else ck
        row = []
        for ki in range(k_hi):
            k0 = ki * bk
            row.append([q0, q_end, qs, k0, min(k0 + bk, sk),
                        min(k0, sk_p - bk), 0, 0])
        row[0][6] = 1
        row[-1][7] = 1
        tiles.extend(tuple(r) for r in row)
    return FlashTileSchedule(sq=sq, sk=sk, bq=bq, bk=bk, causal=causal,
                             tiles=tuple(tiles), sq_p=sq_p, sk_p=sk_p,
                             q_align=proven_align(t[2] for t in tiles),
                             k_align=proven_align(t[5] for t in tiles))


# ---------------------------------------------------------------------------
# In-kernel predication helpers (shared by every fused kernel body)
# ---------------------------------------------------------------------------

def clamped_k_window(ks, bk: int, k: int):
    """Two-step K load: ``(k0, kstart)`` for K-panel ``ks`` over a staged
    K extent ``k``.

    ``k0`` is the nominal panel start; ``kstart`` the clamped origin of
    the fixed-``bk`` window (the last panel slides inward instead of
    shrinking).  When they differ the window revisits lanes the previous
    panel already summed — mask with :func:`k_tail_mask`.
    """
    k0 = ks * bk
    return k0, jnp.minimum(k0, k - bk)


def k_tail_mask(x, axis: int, k0, kstart, k: int):
    """Predicate a K window: keep only lanes in ``[k0, k)`` — at/after the
    nominal panel start (the clamped overlap) and before the operand's
    real K extent (the staged buffer's padding).  ``where`` (not
    multiply) because both may hold non-finite data."""
    kk = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis) + kstart
    return jnp.where((kk >= k0) & (kk < k), x, 0)


def ownership_mask(shape: Tuple[int, int], rs, cs, row0, row_end,
                   col0, col_end):
    """Boolean mask of the window elements this tile *owns* (the predicate
    that keeps every output element owned by exactly one tile)."""
    rows = jax.lax.broadcasted_iota(jnp.int32, shape, 0) + rs
    cols = jax.lax.broadcasted_iota(jnp.int32, shape, 1) + cs
    return ((rows >= row0) & (rows < row_end)
            & (cols >= col0) & (cols < col_end))


def predicated_store(ref, idx, values, own):
    """Predicated two-step RMW store: write only owned elements of the
    clamped window, preserving neighbours written by other tiles."""
    old = ref[idx]
    ref[idx] = jnp.where(own, values, old)


# ---------------------------------------------------------------------------
# Launch accounting
# ---------------------------------------------------------------------------

def plan_launches(plan, fused: bool) -> int:
    """``pallas_call`` count one plan's lowering emits.

    Fused lowerings are single-launch by construction; a multi-launch
    dense plan pays one dispatch per region.  Executors report this via
    ``engine.count_launches`` and cost models charge it at
    ``machine.launch_overhead_s``.
    """
    if fused:
        return 1
    regions = getattr(plan, "regions", None)
    return len(regions) if regions is not None else 1
