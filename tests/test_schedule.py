"""The schedule layer (DESIGN.md §9/§10): property-style validation of
the dense, grouped and flash tile schedules, table packing, and launch
accounting."""
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro.core import (GemmDescriptor, GroupedGemmDescriptor,
                        GroupedTileSchedule, plan_gemm, plan_grouped)
from repro.core.schedule import (QUANT_TILE, TILE_COMPUTE, TILE_SKIP,
                                 TILE_ZERO, ceil_div, flash_tile_schedule,
                                 flatten_regions, pack_table,
                                 plan_launches)


# ---------------------------------------------------------------------------
# Dense (GEMM) schedules
# ---------------------------------------------------------------------------

def _check_gemm_schedule(m, n, k):
    """Every C cell owned by exactly one tile; windows in bounds; the
    packed scalar-prefetch table is int32."""
    plan = plan_gemm(GemmDescriptor(m=m, n=n, k=k))
    sched = plan.tile_schedule()
    sched.validate()  # exact ownership + in-bounds clamped windows
    assert sched.bk <= k and sched.k_steps == ceil_div(k, sched.bk)
    # cell-exact ownership (validate() checks areas; this checks cells)
    owned = np.zeros((m, n), dtype=np.int64)
    for row0, col0, row_end, col_end, rs, cs, bid, sidx in sched.tiles:
        owned[row0:row_end, col0:col_end] += 1
        assert sidx == rs // QUANT_TILE
    assert (owned == 1).all()
    table = pack_table(sched.tiles)
    assert table.dtype == np.int32 and table.shape == (sched.num_tiles, 8)


_GEMM_CASES = [(1, 1, 1), (7, 33, 100), (128, 128, 128), (300, 500, 128),
               (513, 129, 257), (80, 80, 512), (1, 2048, 64), (640, 640, 512)]


if HAVE_HYPOTHESIS:
    @settings(max_examples=60, deadline=None)
    @given(m=st.integers(1, 1024), n=st.integers(1, 1024),
           k=st.integers(1, 2048))
    def test_gemm_schedule_ownership(m, n, k):
        _check_gemm_schedule(m, n, k)
else:
    @pytest.mark.parametrize("m,n,k", _GEMM_CASES)
    def test_gemm_schedule_ownership(m, n, k):
        _check_gemm_schedule(m, n, k)


def test_flatten_regions_matches_plan_tile_schedule():
    """BlockingPlan.tile_schedule delegates to the schedule layer."""
    plan = plan_gemm(GemmDescriptor(m=640, n=640, k=512),
                     force_block=(256, 256))
    d = plan.desc
    assert plan.tile_schedule() == flatten_regions(d.m, d.n, d.k, plan.bk,
                                                   plan.regions)


def test_pack_table_rejects_flat_rows():
    with pytest.raises(AssertionError):
        pack_table([1, 2, 3])


# ---------------------------------------------------------------------------
# Grouped (ragged) schedules
# ---------------------------------------------------------------------------

def _check_grouped_tables(sizes, t_extra, bm=16):
    """Runtime tables from group_sizes: every output row owned exactly
    once (compute rows by their expert, tail rows by zero-fill tiles),
    windows in bounds, int32 packing."""
    sizes = np.asarray(sizes, dtype=np.int32)
    t = max(1, int(sizes.sum()) + t_extra)
    sched = GroupedTileSchedule(t=t, k=32, n=48, num_experts=len(sizes),
                                bm=min(bm, t), bk=32, bn=48)
    import jax.numpy as jnp
    table = np.asarray(sched.tables(jnp.asarray(sizes)))
    assert table.dtype == np.int32
    sched.validate_tables(table, sizes)
    # State accounting: zero tiles iff rows are left over.
    states = table[:, 4]
    assert ((states == TILE_ZERO).any()) == (int(sizes.sum()) < t)
    assert (states != TILE_SKIP).sum() <= sched.max_tiles


_GROUPED_CASES = [
    ([37, 0, 201, 70], 4),   # ragged + zero-size expert + tail rows
    ([0, 0, 0], 5),          # all experts empty: pure zero-fill
    ([300], 0),              # single expert owns all rows
    ([5, 3, 2, 1], 0),       # sub-block groups, no tail
    ([0, 0, 17], 10),        # leading empties + tail
    ([1], 0),                # minimal
]


if HAVE_HYPOTHESIS:
    @settings(max_examples=40, deadline=None)
    @given(sizes=st.lists(st.integers(0, 70), min_size=1, max_size=6),
           t_extra=st.integers(0, 20))
    def test_grouped_tables_ownership(sizes, t_extra):
        _check_grouped_tables(sizes, t_extra)
else:
    @pytest.mark.parametrize("sizes,t_extra", _GROUPED_CASES)
    def test_grouped_tables_ownership(sizes, t_extra):
        _check_grouped_tables(sizes, t_extra)


def test_grouped_schedule_static_bounds():
    """max_tiles is a static bound: every expert may add one partial
    block plus the zero-fill tail — never exceeded, even adversarially."""
    sched = GroupedTileSchedule(t=100, k=32, n=32, num_experts=4,
                                bm=16, bk=32, bn=32)
    assert sched.max_tiles == ceil_div(100, 16) + 4 + 1
    import jax.numpy as jnp
    worst = jnp.asarray([1, 1, 1, 97], jnp.int32)  # max partial blocks
    table = np.asarray(sched.tables(worst))
    assert (table[:, 4] != TILE_SKIP).sum() <= sched.max_tiles
    sched.validate_tables(table, np.asarray(worst))


def test_grouped_plan_tile_schedule_clamps_blocks():
    """Plan blocks larger than the problem clamp so windows fit."""
    desc = GroupedGemmDescriptor(t=7, k=9, n=11, num_experts=2)
    plan = plan_grouped(desc)
    sched = plan.tile_schedule()
    assert sched.bm <= 7 and sched.bk <= 9 and sched.bn <= 11


def test_grouped_compute_tiles_never_cross_experts():
    """A compute tile's owned rows all belong to one expert — the
    property that lets the kernel pull a single weight panel per tile."""
    import jax.numpy as jnp
    sizes = np.asarray([13, 7, 0, 21], np.int32)
    sched = GroupedTileSchedule(t=50, k=16, n=16, num_experts=4,
                                bm=8, bk=16, bn=16)
    table = np.asarray(sched.tables(jnp.asarray(sizes)))
    offsets = np.concatenate([[0], np.cumsum(sizes)])
    for row0, row_end, rs, expert, state in table:
        if state != TILE_COMPUTE:
            continue
        assert offsets[expert] <= row0 and row_end <= offsets[expert + 1]


# ---------------------------------------------------------------------------
# Flash (causal-aware) schedules
# ---------------------------------------------------------------------------

def _check_flash_schedule(sq, sk, bq, bk, causal):
    """Every query row drained exactly once; causal k-blocks above the
    diagonal dropped at plan time; every kept (q, k) pair that the dense
    grid would compute is covered by exactly one tile's [k0, k_end)."""
    sched = flash_tile_schedule(sq, sk, bq, bk, causal)
    sched.validate()
    assert sched.num_tiles <= sched.dense_tiles
    # column coverage per q-block: union of [k0, k_end) over its tiles
    # equals the visible prefix of [0, sk)
    cover = {}
    for q0, q_end, qs, k0, k_end, ks, first, last in sched.tiles:
        cover.setdefault((q0, q_end), []).append((k0, k_end))
    for (q0, q_end), spans in cover.items():
        hit = np.zeros(sk, np.int64)
        for k0, k_end in spans:
            hit[k0:k_end] += 1
        if causal:
            # every column visible to the last owned row is covered once
            visible = min(sk, q_end)
            assert (hit[:visible] == 1).all()
            assert (hit[min(sk, ceil_div(q_end, sched.bk) * sched.bk):]
                    == 0).all()
        else:
            assert (hit == 1).all()


_FLASH_CASES = [
    (256, 256, 128, 128, True), (96, 96, 64, 64, True),
    (100, 100, 64, 32, True), (130, 70, 64, 32, False),
    (1, 1, 64, 64, True), (7, 300, 8, 128, True), (512, 512, 128, 64, False),
]


if HAVE_HYPOTHESIS:
    @settings(max_examples=60, deadline=None)
    @given(sq=st.integers(1, 600), sk=st.integers(1, 600),
           bq=st.sampled_from([8, 32, 64, 128]),
           bk=st.sampled_from([8, 32, 64, 128]),
           causal=st.booleans())
    def test_flash_schedule_coverage(sq, sk, bq, bk, causal):
        _check_flash_schedule(sq, sk, bq, bk, causal)
else:
    @pytest.mark.parametrize("sq,sk,bq,bk,causal", _FLASH_CASES)
    def test_flash_schedule_coverage(sq, sk, bq, bk, causal):
        _check_flash_schedule(sq, sk, bq, bk, causal)


def test_flash_schedule_causal_drops_tiles():
    """The causal triangle drops ~half the dense grid at plan time — the
    acceptance property the launch/step savings rest on."""
    sched = flash_tile_schedule(2048, 2048, 128, 128, causal=True)
    assert sched.num_tiles < sched.dense_tiles
    # 16x16 grid: lower triangle = 136 of 256
    assert sched.dense_tiles == 256 and sched.num_tiles == 136
    dense = flash_tile_schedule(2048, 2048, 128, 128, causal=False)
    assert dense.num_tiles == dense.dense_tiles == 256
    table = pack_table(sched.tiles)
    assert table.dtype == np.int32 and table.shape == (136, 8)


# ---------------------------------------------------------------------------
# Decode (paged) schedules + the page allocator (DESIGN.md §12)
# ---------------------------------------------------------------------------

from repro.core.schedule import DecodeTileSchedule
from repro.models.attention import PageSpec
from repro.runtime.pages import OutOfPages, PagePool, pages_for


def _pool_for(lengths, page_size, extra_pages=0, max_blocks=1):
    """A pool sized to hold ``lengths``, with every slot grown to its
    length — the allocator state one scheduler tick would produce."""
    need = [pages_for(L, page_size) for L in lengths]
    spec = PageSpec(num_pages=max(1, sum(need) + extra_pages),
                    page_size=page_size,
                    max_blocks=max(max_blocks, max(need, default=1)))
    pool = PagePool(spec, len(lengths))
    for i, L in enumerate(lengths):
        pool.grow(i, L)
    return pool, spec


def _check_decode_tables(lengths, page_size, extra_pages, pages_per_step):
    """Rows visit each live page exactly once in block-table order,
    ``pages_per_step`` at a time, live lengths are exact, carries
    bracket, rows past the live total are inert — and the allocator's
    invariants hold after building the state."""
    pool, spec = _pool_for(lengths, page_size, extra_pages,
                           max_blocks=pages_per_step)
    pool.check_invariants(list(lengths))
    sched = DecodeTileSchedule(num_seqs=len(lengths), pages=spec.num_pages,
                               page_size=page_size,
                               max_blocks=spec.max_blocks,
                               pages_per_step=pages_per_step)
    import jax.numpy as jnp
    table = np.asarray(sched.tables(jnp.asarray(lengths, jnp.int32)))
    assert table.dtype == np.int32
    sched.validate_tables(table, pool.tables, np.asarray(lengths))
    # the static bound: at most ceil(max_blocks / pps) rows a slot, and
    # pool-wide one partial block (or empty row) a slot beyond the
    # pool's whole blocks
    assert sched.max_tiles == min(
        len(lengths) * ceil_div(spec.max_blocks, pages_per_step),
        spec.num_pages // pages_per_step + len(lengths))
    assert (table[:, 2] > 0).sum() == sched.live_rows(lengths)


_DECODE_CASES = [
    ([0, 0, 0], 4, 2),       # all slots idle: one dummy row each
    ([5], 4, 0),             # single seq, ragged tail
    ([16, 16], 16, 0),       # exact page multiples
    ([1, 33, 0, 7], 8, 3),   # mixed live/idle, multi-page walk
    ([9, 9, 9, 9, 9], 3, 0), # every seq spans several pages
    ([100], 8, 5),           # long single sequence
]

# Lengths on page and block boundaries (8-token pages, blocks of 2 and 8
# pages), empty slots, and pools with every page live.
_DECODE_BOUNDARY_CASES = _DECODE_CASES + [
    ([8, 16, 0, 24], 8, 0),      # whole pages, every page live
    ([64, 0, 128, 65], 8, 0),    # whole 8-page blocks, one page over
    ([15, 17, 63, 1, 0], 8, 4),  # a token either side of a boundary
]
_PAGES_PER_STEP = [1, 2, 8]


@pytest.mark.parametrize("pages_per_step", _PAGES_PER_STEP)
@pytest.mark.parametrize("lengths,page_size,extra_pages",
                         _DECODE_BOUNDARY_CASES)
def test_decode_tables_boundaries(lengths, page_size, extra_pages,
                                  pages_per_step):
    _check_decode_tables(lengths, page_size, extra_pages, pages_per_step)


if HAVE_HYPOTHESIS:
    @pytest.mark.parametrize("pages_per_step", _PAGES_PER_STEP)
    @settings(max_examples=40, deadline=None)
    @given(lengths=st.lists(st.integers(0, 70), min_size=1, max_size=6),
           page_size=st.sampled_from([1, 4, 8, 16]),
           extra_pages=st.integers(0, 8))
    def test_decode_tables_coverage(lengths, page_size, extra_pages,
                                    pages_per_step):
        _check_decode_tables(lengths, page_size, extra_pages,
                             pages_per_step)
else:
    @pytest.mark.parametrize("pages_per_step", _PAGES_PER_STEP)
    @pytest.mark.parametrize("lengths,page_size,extra_pages", _DECODE_CASES)
    def test_decode_tables_coverage(lengths, page_size, extra_pages,
                                    pages_per_step):
        _check_decode_tables(lengths, page_size, extra_pages,
                             pages_per_step)


@pytest.mark.parametrize("pages_per_step,pages,max_blocks,tiles,active", [
    (1, 5, 4, 5 + 3, 2 + 3 + 1),  # a row a live page, a dummy row a slot
    (2, 5, 4, 5 // 2 + 3, 1 + 2 + 1),
    (8, 20, 8, 3 * 1, 1 + 1 + 1),  # one block a slot: no inert row
])
def test_decode_schedule_static_bounds(pages_per_step, pages, max_blocks,
                                       tiles, active):
    """max_tiles caps the walk: at most ceil(max_blocks / pps) blocks a
    slot, and pool-wide, since live pages are exclusively owned, the
    pool's whole blocks plus one partial (or dummy) row a slot."""
    sched = DecodeTileSchedule(num_seqs=3, pages=pages, page_size=4,
                               max_blocks=max_blocks,
                               pages_per_step=pages_per_step)
    assert sched.max_tiles == tiles
    assert sched.max_len == 4 * max_blocks
    import jax.numpy as jnp
    bt = np.zeros((3, max_blocks), np.int32)
    bt[0, :2], bt[1, :3] = [0, 1], [2, 3, 4]
    lengths = np.asarray([8, 12, 0])
    table = np.asarray(sched.tables(jnp.asarray(lengths)))
    sched.validate_tables(table, bt, lengths)
    rows = (table[:, 3] | table[:, 4] | (table[:, 2] > 0)).sum()
    assert rows == active <= sched.max_tiles
    # rows past the live total carry nothing and stay on the last live
    # row's query row
    inert = table[active:]
    assert (inert[:, 2:] == 0).all() and (inert[:, 0] == 2).all()


def test_decode_tables_lower_without_a_loop():
    """The table is built in every decode step, so it must lower to
    straight-line ops: a binary search would be a while loop of tiny
    device ops (qwen3-0.6b.chat's geometry)."""
    import jax
    import jax.numpy as jnp
    sched = DecodeTileSchedule(num_seqs=24, pages=1792, page_size=16,
                               max_blocks=112, pages_per_step=16)
    text = jax.jit(sched.tables).lower(
        jax.ShapeDtypeStruct((24,), jnp.int32)).as_text()
    assert "while" not in text


def _check_pool_ops(ops, page_size, num_pages, num_slots):
    """Allocator conservation under an arbitrary grow/release trace: no
    page double-owned, free list + live pages exactly partition the
    pool, block tables cover exactly ceil(len/page) pages per slot."""
    spec = PageSpec(num_pages=num_pages, page_size=page_size,
                    max_blocks=num_pages)
    pool = PagePool(spec, num_slots)
    lengths = [0] * num_slots
    for kind, slot, length in ops:
        slot %= num_slots
        if kind == "grow":
            try:
                pool.grow(slot, length)
                lengths[slot] = max(lengths[slot], length)
            except (OutOfPages, ValueError):
                pass  # rejected (queue / unmappable) — state untouched
        else:
            pool.release(slot)
            lengths[slot] = 0
        pool.check_invariants(lengths)
        for i in range(num_slots):
            assert pool.slot_blocks(i) == pages_for(lengths[i], page_size)
    assert pool.free_pages == num_pages - sum(
        pages_for(L, page_size) for L in lengths)


_POOL_CASES = [
    ([("grow", 0, 9), ("grow", 1, 5), ("release", 0, 0),
      ("grow", 2, 12), ("release", 1, 0), ("grow", 0, 3)], 4, 6, 3),
    ([("grow", 0, 50)], 4, 2, 1),  # oversized growth only queues
    ([("grow", 0, 8), ("grow", 0, 8), ("release", 0, 0),
      ("release", 0, 0)], 8, 2, 1),  # idempotent re-release
]


if HAVE_HYPOTHESIS:
    @settings(max_examples=40, deadline=None)
    @given(ops=st.lists(
        st.tuples(st.sampled_from(["grow", "release"]),
                  st.integers(0, 5), st.integers(0, 40)),
        min_size=1, max_size=20),
        page_size=st.sampled_from([2, 4, 8]),
        num_pages=st.integers(1, 12),
        num_slots=st.integers(1, 4))
    def test_page_pool_conservation(ops, page_size, num_pages, num_slots):
        _check_pool_ops(ops, page_size, num_pages, num_slots)
else:
    @pytest.mark.parametrize("ops,page_size,num_pages,num_slots",
                             _POOL_CASES)
    def test_page_pool_conservation(ops, page_size, num_pages, num_slots):
        _check_pool_ops(ops, page_size, num_pages, num_slots)


def test_page_pool_faults():
    """OutOfPages when the free list runs dry; ValueError when a length
    can never be mapped; release frees exactly the victim's pages."""
    pool = PagePool(PageSpec(num_pages=4, page_size=4, max_blocks=3), 2)
    pool.grow(0, 12)  # 3 pages
    assert pool.free_pages == 1
    with pytest.raises(OutOfPages):
        pool.grow(1, 8)  # needs 2, only 1 free
    assert pool.free_pages == 1  # failed growth must not leak
    with pytest.raises(ValueError):
        pool.grow(1, 13)  # 4 pages > max_blocks
    assert pool.release(0) == 3
    assert pool.free_pages == 4
    assert pool.grow(1, 8) and pool.slot_blocks(1) == 2
    pool.check_invariants([0, 8])


def test_pages_for():
    assert [pages_for(L, 4) for L in (0, 1, 4, 5, 8)] == [0, 1, 1, 2, 2]


# ---------------------------------------------------------------------------
# Launch accounting
# ---------------------------------------------------------------------------

def test_plan_launches():
    gemm_plan = plan_gemm(GemmDescriptor(m=640, n=640, k=512),
                          force_block=(256, 256))
    assert len(gemm_plan.regions) >= 3
    assert plan_launches(gemm_plan, fused=True) == 1
    assert plan_launches(gemm_plan, fused=False) == len(gemm_plan.regions)
    grouped = plan_grouped(GroupedGemmDescriptor(t=64, k=32, n=32,
                                                 num_experts=2))
    # both grouped lowerings are single pallas_calls (pad/scatter pays in
    # stitch traffic, not launches)
    assert plan_launches(grouped, fused=True) == 1
    assert plan_launches(grouped, fused=False) == 1
