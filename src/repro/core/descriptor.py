"""Kernel descriptors — the LIBXSMM ``libxsmm_gemm_descriptor`` analogue.

The paper's JIT code generator "hardwires matrix sizes, datatypes, and
leading dimensions when generating a matrix kernel" (§IV).  A descriptor
carries exactly that metadata; it is the hashable key of both engine
caches (plan + kernel, see ``repro.core.engine``) and the input of the
blocking planners (``repro.core.blocking``).

Every kernel family the engine dispatches — dense GEMM, flash attention,
ragged grouped GEMM, the SSD intra-chunk ladder, and tile transpose — has
one frozen-dataclass descriptor here, all deriving from
:class:`KernelDescriptor`.  Each carries flops/bytes accounting so
``launch/roofline.py`` and ``launch/hlo_cost.py`` can cost any kernel in
the system, not just GEMMs (DESIGN.md §2).

Layout semantics.  JAX arrays are logically row-major.  We express the
paper's two studied layouts as contraction forms:

  * ``"nn"`` — ``C[M,N] += A[M,K] @ B[K,N]``: the contraction dim of B is
    its *major* dim.  This corresponds to the paper's row-major-B case
    (§IV-A): B's N-slice for one k is contiguous, outer-product friendly.
  * ``"nt"`` — ``C[M,N] += A[M,K] @ B[N,K]^T``: B stores N major / K minor.
    This is the paper's "transposing B" case (§IV-C): the contraction dim
    is strided, so the kernel must either transpose panels through scratch
    (the ZA horizontal/vertical trick) or fuse a block transpose.

(The paper's column-major `A/C`, row-major `B` maps onto "nn" under a
global transpose of the problem; what matters — and what we preserve — is
whether B's contraction dim is contiguous.)
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax.numpy as jnp

from .machine import canonical_dtype

LAYOUTS = ("nn", "nt")
EPILOGUES = (None, "bias", "gelu", "silu", "relu", "bias_gelu", "bias_silu")
BIAS_EPILOGUES = tuple(e for e in EPILOGUES if e and e.startswith("bias"))

QUANT_DTYPES = ("int8", "float8_e4m3")
QUANT_SCHEMES = ("per_tensor", "per_channel", "per_tile")

# String shorthands accepted anywhere a quant spec is (config knob,
# REPRO_QUANT, gemm(quant=...)).
_QUANT_ALIASES = {
    "int8": ("int8", False),
    "w8a16": ("int8", True),
    "fp8": ("float8_e4m3", False),
    "float8_e4m3": ("float8_e4m3", False),
}


@dataclasses.dataclass(frozen=True)
class QuantSpec:
    """Low-precision execution spec carried by GEMM-family descriptors
    (DESIGN.md §13).

    ``dtype`` is the *wire* dtype the quantized operand(s) are stored and
    staged in; accumulation always happens wide (int32 for int8 inputs,
    f32 otherwise) and dequantization fuses into the shared epilogue.
    ``scheme`` fixes how scales partition operand channels — per-tensor
    (scalar), per-channel (one scale per A row / B output column), or
    per-tile (one scale per ``QUANT_TILE``-sized channel block; see
    ``repro.core.schedule.QUANT_TILE``).  All three are row/col-separable,
    which is what lets the dequant commute through the contraction and
    live in the epilogue.  ``weight_only`` quantizes only the B operand
    (W8A16): A stays in ``in_dtype``, B is dequantized in-kernel before
    the MXU dot, and the column scales still apply in the epilogue.
    """

    dtype: str = "int8"
    scheme: str = "per_channel"
    weight_only: bool = False

    def __post_init__(self):
        if self.dtype not in QUANT_DTYPES:
            raise ValueError(
                f"quant dtype must be one of {QUANT_DTYPES}, got {self.dtype}")
        if self.scheme not in QUANT_SCHEMES:
            raise ValueError(
                f"quant scheme must be one of {QUANT_SCHEMES}, "
                f"got {self.scheme}")
        if self.dtype == "float8_e4m3" and not hasattr(jnp, "float8_e4m3fn"):
            raise ValueError(
                "float8_e4m3 quantization needs a jax build with "
                "jnp.float8_e4m3fn (gate callers on "
                "repro.core.machine.HAS_FP8)")

    @property
    def wire_itemsize(self) -> int:
        """Bytes per element of the quantized wire format (1 for both
        int8 and fp8)."""
        return 1


def resolve_quant(quant) -> Optional[QuantSpec]:
    """Normalize a quant argument: None/False → None, a string shorthand
    (``"int8"``/``"w8a16"``/``"fp8"``) → the matching :class:`QuantSpec`,
    a spec → itself."""
    if quant is None or quant is False:
        return None
    if isinstance(quant, QuantSpec):
        return quant
    if isinstance(quant, str):
        if quant not in _QUANT_ALIASES:
            raise ValueError(
                f"unknown quant shorthand {quant!r}; expected one of "
                f"{sorted(_QUANT_ALIASES)} or a QuantSpec")
        dtype, weight_only = _QUANT_ALIASES[quant]
        return QuantSpec(dtype=dtype, weight_only=weight_only)
    raise ValueError(f"quant must be None, a str or a QuantSpec, got "
                     f"{type(quant).__name__}")


def check_bias(epilogue, bias) -> None:
    """Shared precondition: a bias-consuming epilogue needs a bias operand."""
    if epilogue in BIAS_EPILOGUES and bias is None:
        raise ValueError(
            f"epilogue {epilogue!r} requires a bias operand, got bias=None")


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Mesh placement carried by GEMM-family descriptors (DESIGN.md §14).

    ``axis`` names the mesh axis the weight operand is sharded over
    (expert dim for grouped GEMM, output-column dim for dense GEMM) and
    ``size`` is that axis's extent.  ``None`` mesh on a descriptor means
    the single-chip problem the planner always handled; a ``MeshSpec``
    makes the *global* problem the descriptor's subject, and the planner
    charges communication (all-gather vs. all_to_all) to pick between a
    *gathered* and a *distributed* execution.  Frozen + hashable, so it
    participates in every cache key via ``KernelDescriptor.cache_key``.
    """

    axis: str = "model"
    size: int = 1

    def __post_init__(self):
        if not self.axis:
            raise ValueError("mesh axis name must be non-empty")
        if self.size < 1:
            raise ValueError(f"mesh size must be >= 1, got {self.size}")


@dataclasses.dataclass(frozen=True)
class KernelDescriptor:
    """Base of every per-family descriptor.

    Subclasses are frozen dataclasses — hashable and equality-comparable by
    value — and set ``family`` to the engine registry name.  The engine
    derives both cache keys (plan and kernel) from :meth:`cache_key`, so no
    family hand-writes a key tuple.
    """

    family = "abstract"

    def cache_key(self) -> tuple:
        return (self.family,) + dataclasses.astuple(self)

    # flops/bytes accounting — subclasses override; base gives the shared
    # derived metric.
    @property
    def flops(self) -> int:
        raise NotImplementedError

    @property
    def in_bytes(self) -> int:
        raise NotImplementedError

    @property
    def out_bytes(self) -> int:
        raise NotImplementedError

    @property
    def arithmetic_intensity(self) -> float:
        return self.flops / max(1, self.in_bytes + self.out_bytes)


@dataclasses.dataclass(frozen=True)
class GemmDescriptor(KernelDescriptor):
    """Hashable metadata fully specifying one generated GEMM kernel."""

    family = "gemm"

    m: int
    n: int
    k: int
    layout: str = "nn"  # "nn": B is (K,N); "nt": B is (N,K)
    in_dtype: str = "float32"
    acc_dtype: str = "float32"
    out_dtype: str = "float32"
    accumulate: bool = False  # True => C += A@B (beta=1), else C = A@B
    epilogue: Optional[str] = None
    # Edge-handling strategy: "mask" (predication analogue) or "pad"
    # (copy-based).  §IV-B uses predicates; we support both to benchmark.
    edge: str = "mask"
    # batch dims (leading, shared by A/B/C); 0 => unbatched 2-D GEMM
    batch: int = 0
    # Low-precision execution axis (DESIGN.md §13); None = wide GEMM.
    quant: Optional[QuantSpec] = None
    # Mesh placement (DESIGN.md §14): B's output-column (n) dim sharded
    # over mesh.axis; None = the single-chip problem.
    mesh: Optional[MeshSpec] = None

    def __post_init__(self):
        if self.mesh is not None:
            if not isinstance(self.mesh, MeshSpec):
                raise ValueError(f"mesh must be a MeshSpec, got {self.mesh!r}")
            if self.n % self.mesh.size:
                raise ValueError(f"mesh size {self.mesh.size} must divide "
                                 f"n={self.n}")
        if self.layout not in LAYOUTS:
            raise ValueError(f"layout must be one of {LAYOUTS}, got {self.layout}")
        if self.epilogue not in EPILOGUES:
            raise ValueError(f"epilogue must be one of {EPILOGUES}")
        if self.edge not in ("mask", "pad"):
            raise ValueError("edge must be 'mask' or 'pad'")
        for d in (self.m, self.n, self.k):
            if d <= 0:
                raise ValueError(f"GEMM dims must be positive, got {self}")
        if self.quant is not None:
            if not isinstance(self.quant, QuantSpec):
                raise ValueError(f"quant must be a QuantSpec, got {self.quant!r}")
            if self.accumulate:
                raise ValueError("quantized GEMM does not support accumulate "
                                 "(C += A@B); dequant owns the epilogue")
            if self.batch:
                raise ValueError("quantized GEMM is unbatched (scale vectors "
                                 "are per-row/per-column of one problem)")
            if self.edge != "mask":
                raise ValueError("quantized GEMM requires edge='mask'")

    # -- constructors -------------------------------------------------------
    @classmethod
    def from_operands(cls, a, b, layout="nn", accumulate=False, epilogue=None,
                      acc_dtype="float32", out_dtype=None, edge="mask",
                      quant=None):
        if a.ndim != b.ndim:
            raise ValueError(f"rank mismatch: A{a.shape} vs B{b.shape}")
        batch = 0
        if a.ndim == 3:
            if a.shape[0] != b.shape[0]:
                raise ValueError(f"batch mismatch: A{a.shape} vs B{b.shape}")
            batch = a.shape[0]
        elif a.ndim != 2:
            raise ValueError(f"GEMM operands must be rank 2 or 3, got {a.ndim}")
        m, k = a.shape[-2], a.shape[-1]
        if layout == "nn":
            kb, n = b.shape[-2], b.shape[-1]
        else:
            n, kb = b.shape[-2], b.shape[-1]
        if kb != k:
            raise ValueError(f"contraction mismatch: A{a.shape} {layout} B{b.shape}")
        quant = resolve_quant(quant)
        in_dtype = canonical_dtype(a.dtype)
        if quant is not None and quant.weight_only:
            # W8A16: B arrives in (or will be quantized to) the wire
            # dtype while A stays wide — the equality check is the wide
            # path's invariant, not this one's.
            pass
        elif canonical_dtype(b.dtype) != in_dtype:
            raise ValueError(f"A/B dtype mismatch: {a.dtype} vs {b.dtype}")
        return cls(
            m=m, n=n, k=k, layout=layout, in_dtype=in_dtype,
            acc_dtype=canonical_dtype(acc_dtype),
            out_dtype=canonical_dtype(out_dtype or acc_dtype),
            accumulate=accumulate, epilogue=epilogue, edge=edge, batch=batch,
            quant=quant,
        )

    # -- properties ----------------------------------------------------------
    @property
    def flops(self) -> int:
        nb = max(1, self.batch)
        return 2 * nb * self.m * self.n * self.k

    @property
    def a_wire_itemsize(self) -> int:
        """Bytes per staged A element: the quant wire format for a fully
        quantized GEMM, ``in_dtype`` otherwise (W8A16 keeps A wide)."""
        if self.quant is not None and not self.quant.weight_only:
            return self.quant.wire_itemsize
        return jnp.dtype(self.in_dtype).itemsize

    @property
    def b_wire_itemsize(self) -> int:
        """Bytes per staged B element (any quant spec narrows B)."""
        if self.quant is not None:
            return self.quant.wire_itemsize
        return jnp.dtype(self.in_dtype).itemsize

    @property
    def compute_dtype(self) -> str:
        """The dtype whose machine peak prices the MXU work: the quant
        wire dtype for fully quantized GEMMs (int8 MACs), ``in_dtype``
        for wide and weight-only GEMMs (W8A16 dequantizes B before the
        dot)."""
        if self.quant is not None and not self.quant.weight_only:
            return self.quant.dtype
        return self.in_dtype

    @property
    def in_bytes(self) -> int:
        nb = max(1, self.batch)
        total = nb * (self.m * self.k * self.a_wire_itemsize
                      + self.k * self.n * self.b_wire_itemsize)
        if self.quant is not None:
            # f32 dequant scale vectors staged alongside the operands.
            total += (self.m + self.n) * 4
        return total

    @property
    def out_bytes(self) -> int:
        nb = max(1, self.batch)
        return nb * self.m * self.n * jnp.dtype(self.out_dtype).itemsize

    def b_shape(self) -> tuple:
        core = (self.k, self.n) if self.layout == "nn" else (self.n, self.k)
        return (self.batch, *core) if self.batch else core

    def a_shape(self) -> tuple:
        core = (self.m, self.k)
        return (self.batch, *core) if self.batch else core

    def c_shape(self) -> tuple:
        core = (self.m, self.n)
        return (self.batch, *core) if self.batch else core


# ---------------------------------------------------------------------------
# Non-GEMM families
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class FlashDescriptor(KernelDescriptor):
    """Flash-attention forward: (BH, sq, d) x (BH, sk, d)^2 -> (BH, sq, d)."""

    family = "flash_attention"

    batch_heads: int
    sq: int
    sk: int
    d: int
    causal: bool = True
    dtype: str = "float32"

    def __post_init__(self):
        for v in (self.batch_heads, self.sq, self.sk, self.d):
            if v <= 0:
                raise ValueError(f"flash dims must be positive, got {self}")

    @classmethod
    def from_operands(cls, q, k, *, causal=True):
        b, sq, h, d = q.shape
        return cls(batch_heads=b * h, sq=sq, sk=k.shape[1], d=d,
                   causal=causal, dtype=canonical_dtype(q.dtype))

    @property
    def flops(self) -> int:
        # QK^T and PV are each 2*sq*sk*d MACs; causal masking halves the
        # useful score area (the kernel skips fully-masked tiles).
        full = 4 * self.batch_heads * self.sq * self.sk * self.d
        return full // 2 if self.causal else full

    @property
    def in_bytes(self) -> int:
        isz = jnp.dtype(self.dtype).itemsize
        return self.batch_heads * (self.sq + 2 * self.sk) * self.d * isz

    @property
    def out_bytes(self) -> int:
        return self.batch_heads * self.sq * self.d * jnp.dtype(self.dtype).itemsize


@dataclasses.dataclass(frozen=True)
class FlashDecodeDescriptor(KernelDescriptor):
    """Paged decode attention (continuous batching, DESIGN.md §12):
    one query row per slot against that slot's live KV pages.

    ``(q: (S, h, hd))`` x ``(k/v pool: (pages, page_size, hkv, hd))``
    -> ``(S, h, hd)``, mapped by runtime ``(block_tables, lengths)``
    operands.  Like the grouped-GEMM family, the *ragged part is data*:
    the descriptor carries only the static pool geometry, so the kernel
    is built once per (pool, heads) shape and the churning batch rides
    through as scalar-prefetch tables (no retrace on admission/eviction).
    """

    family = "flash_decode"

    num_seqs: int     # decode slots
    pages: int        # pool size in pages
    page_size: int    # KV slots per page
    max_blocks: int   # block-table width
    num_heads: int
    num_kv_heads: int
    head_dim: int
    dtype: str = "float32"

    def __post_init__(self):
        for v in (self.num_seqs, self.pages, self.page_size,
                  self.max_blocks, self.num_heads, self.num_kv_heads,
                  self.head_dim):
            if v <= 0:
                raise ValueError(f"decode dims must be positive, got {self}")
        if self.num_heads % self.num_kv_heads:
            raise ValueError(f"GQA group must divide heads, got {self}")

    @classmethod
    def from_operands(cls, q, k_pool, block_tables):
        s, h, hd = q.shape
        # one layer's pool, or the last four dims of a stack of them
        pages, page_size, hkv, _ = k_pool.shape[-4:]
        return cls(num_seqs=s, pages=pages, page_size=page_size,
                   max_blocks=block_tables.shape[1], num_heads=h,
                   num_kv_heads=hkv, head_dim=hd,
                   dtype=canonical_dtype(q.dtype))

    @property
    def flops(self) -> int:
        # QK^T and PV over every pool page (the worst case: all pages
        # live); actual walked tiles are bounded by the same number since
        # live pages are exclusively owned.
        return 4 * self.num_heads * self.head_dim * self.pages \
            * self.page_size

    @property
    def in_bytes(self) -> int:
        isz = jnp.dtype(self.dtype).itemsize
        q = self.num_seqs * self.num_heads * self.head_dim * isz
        kv = 2 * self.pages * self.page_size * self.num_kv_heads \
            * self.head_dim * isz
        tables = self.num_seqs * (self.max_blocks + 1) * 4
        return q + kv + tables

    @property
    def out_bytes(self) -> int:
        return self.num_seqs * self.num_heads * self.head_dim \
            * jnp.dtype(self.dtype).itemsize


@dataclasses.dataclass(frozen=True)
class GroupedGemmDescriptor(KernelDescriptor):
    """Ragged grouped GEMM (MoE expert compute): (T, K) x (E, K, N) -> (T, N).

    ``t`` is the static row count; the per-group split (``group_sizes``) is
    a runtime operand and deliberately NOT part of the descriptor — the
    kernel is shape-specialized, the routing is data (DESIGN.md §2).
    ``epilogue`` mirrors the GEMM vocabulary; the ``bias`` operand is
    per-expert, shape (E, N).
    """

    family = "grouped_gemm"

    t: int
    k: int
    n: int
    num_experts: int
    dtype: str = "float32"
    epilogue: Optional[str] = None
    # Low-precision execution axis (DESIGN.md §13); None = wide GEMM.
    quant: Optional[QuantSpec] = None
    # Mesh placement (DESIGN.md §14): the expert dim sharded over
    # mesh.axis; ``t``/``num_experts`` describe the GLOBAL problem and
    # the planner derives the per-shard sub-problems it costs.
    mesh: Optional[MeshSpec] = None

    def __post_init__(self):
        for v in (self.t, self.k, self.n, self.num_experts):
            if v <= 0:
                raise ValueError(f"grouped-GEMM dims must be positive, got {self}")
        if self.epilogue not in EPILOGUES:
            raise ValueError(f"epilogue must be one of {EPILOGUES}")
        if self.quant is not None and not isinstance(self.quant, QuantSpec):
            raise ValueError(f"quant must be a QuantSpec, got {self.quant!r}")
        if self.mesh is not None:
            if not isinstance(self.mesh, MeshSpec):
                raise ValueError(f"mesh must be a MeshSpec, got {self.mesh!r}")
            if self.num_experts % self.mesh.size or self.t % self.mesh.size:
                raise ValueError(
                    f"mesh size {self.mesh.size} must divide both "
                    f"num_experts={self.num_experts} and t={self.t}")

    @classmethod
    def from_operands(cls, x, w, epilogue=None, quant=None, mesh=None):
        t, k = x.shape
        e, kw, n = w.shape
        if kw != k:
            raise ValueError(f"contraction mismatch: x{x.shape} vs w{w.shape}")
        return cls(t=t, k=k, n=n, num_experts=e,
                   dtype=canonical_dtype(x.dtype), epilogue=epilogue,
                   quant=resolve_quant(quant), mesh=mesh)

    @property
    def x_wire_itemsize(self) -> int:
        """Bytes per staged activation-row element (narrow only for a
        fully quantized grouped GEMM)."""
        if self.quant is not None and not self.quant.weight_only:
            return self.quant.wire_itemsize
        return jnp.dtype(self.dtype).itemsize

    @property
    def w_wire_itemsize(self) -> int:
        """Bytes per staged expert-panel element (narrow under any quant
        spec)."""
        if self.quant is not None:
            return self.quant.wire_itemsize
        return jnp.dtype(self.dtype).itemsize

    @property
    def compute_dtype(self) -> str:
        """Dtype pricing the MXU work (see GemmDescriptor.compute_dtype)."""
        if self.quant is not None and not self.quant.weight_only:
            return self.quant.dtype
        return self.dtype

    @property
    def flops(self) -> int:
        # Each row contracts against exactly one expert's (K, N) panel.
        return 2 * self.t * self.k * self.n

    @property
    def in_bytes(self) -> int:
        total = (self.t * self.k * self.x_wire_itemsize
                 + self.num_experts * self.k * self.n * self.w_wire_itemsize)
        if self.quant is not None:
            # per-expert column scales (+ per-row activation scales).
            total += (self.num_experts * self.n + self.t) * 4
        return total

    @property
    def out_bytes(self) -> int:
        return self.t * self.n * jnp.dtype(self.dtype).itemsize


@dataclasses.dataclass(frozen=True)
class SsdChunkDescriptor(KernelDescriptor):
    """SSD (Mamba-2) chunked-scan family, two forms (DESIGN.md §4/§10).

    ``chunks == 0`` — the intra-chunk ladder only (the pre-schedule
    surface): ``(G,Q,n) x2, (G,Q,Q), (G,Q,p) -> (G,Q,p)`` where ``G``
    flattens batch x chunk x head.

    ``chunks >= 1`` — the whole chunked scan: per group (batch x head)
    the kernel walks ``chunks`` sequentially with the inter-chunk state
    ``(p, n)`` carried as accumulator state, consuming
    ``(G, C, Q, n) x2, (G, C, Q, Q), (G, C, Q, p), (G, C, Q) x2`` decay
    vectors and an initial state ``(G, p, n)``, and producing
    ``y: (G, C, Q, p)`` plus the final state ``(G, p, n)``.
    """

    family = "ssd_chunk"

    groups: int
    q: int
    n: int
    p: int
    dtype: str = "float32"
    # number of chunks walked per group with carried state; 0 selects the
    # intra-chunk (diagonal-block) form with no inter-chunk recurrence
    chunks: int = 0

    def __post_init__(self):
        for v in (self.groups, self.q, self.n, self.p):
            if v <= 0:
                raise ValueError(f"SSD dims must be positive, got {self}")
        if self.chunks < 0:
            raise ValueError(f"SSD chunks must be >= 0, got {self}")

    @classmethod
    def from_operands(cls, c_mat, xdt):
        """Descriptor of the intra-chunk form from ``(G,Q,n)``/``(G,Q,p)``
        operands."""
        g, q, n = c_mat.shape
        return cls(groups=g, q=q, n=n, p=xdt.shape[-1],
                   dtype=canonical_dtype(xdt.dtype))

    @classmethod
    def from_scan_operands(cls, c_mat, xdt):
        """Descriptor of the carried-state scan form from
        ``(G,C,Q,n)``/``(G,C,Q,p)`` operands."""
        g, chunks, q, n = c_mat.shape
        return cls(groups=g, q=q, n=n, p=xdt.shape[-1],
                   dtype=canonical_dtype(xdt.dtype), chunks=chunks)

    @property
    def cells(self) -> int:
        """(group, chunk) cells walked: ``G`` for the intra-chunk form,
        ``G * chunks`` for the scan form."""
        return self.groups * max(1, self.chunks)

    @property
    def flops(self) -> int:
        # Intra-chunk ladder per cell: GEMM 1 (Q,n)x(n,Q) + GEMM 2
        # (Q,Q)x(Q,p); the scan form adds the inter-chunk terms y_off
        # (Q,n)x(n,p) and the state outer product (p,Q)x(Q,n).
        intra = 2 * self.q * self.q * (self.n + self.p)
        inter = 4 * self.q * self.n * self.p if self.chunks else 0
        return self.cells * (intra + inter)

    @property
    def in_bytes(self) -> int:
        isz = jnp.dtype(self.dtype).itemsize
        per_cell = 2 * self.q * self.n + self.q * self.q + self.q * self.p
        if self.chunks:
            per_cell += 2 * self.q  # decay_in / decay_out vectors
        total = self.cells * per_cell * isz
        if self.chunks:
            total += self.groups * self.p * self.n * 4  # initial state, fp32
        return total

    @property
    def out_bytes(self) -> int:
        total = self.cells * self.q * self.p * jnp.dtype(self.dtype).itemsize
        if self.chunks:
            total += self.groups * self.p * self.n * 4  # final state, fp32
        return total


@dataclasses.dataclass(frozen=True)
class FlashBwdDescriptor(FlashDescriptor):
    """Flash-attention backward: dO, O, LSE, Q, K, V -> dQ, dK, dV.

    Same geometry fields as :class:`FlashDescriptor` (the backward walk
    reuses the forward ``FlashTileSchedule``), but a distinct ``family`` so
    the engine caches/autotunes/counts backward plans separately
    (DESIGN.md §11).
    """

    family = "flash_attention_bwd"

    @classmethod
    def from_forward(cls, desc: FlashDescriptor) -> "FlashBwdDescriptor":
        """Backward descriptor sharing a forward descriptor's geometry."""
        return cls(**dataclasses.asdict(desc))

    @property
    def flops(self) -> int:
        # Five tile GEMMs per visited (q,k) tile (dV, dP, dQ, dK plus the
        # recomputed P) vs the forward's two — charge 2.5x forward.
        return (5 * super().flops) // 2

    @property
    def in_bytes(self) -> int:
        isz = jnp.dtype(self.dtype).itemsize
        # q/k/v/o/do operand panels plus the staged fp32 LSE rows.
        return (self.batch_heads * (3 * self.sq + 2 * self.sk) * self.d * isz
                + self.batch_heads * self.sq * 4)

    @property
    def out_bytes(self) -> int:
        # dQ (operand dtype) + dK/dV accumulated in fp32.
        isz = jnp.dtype(self.dtype).itemsize
        return self.batch_heads * (self.sq * self.d * isz
                                   + 2 * self.sk * self.d * 4)


@dataclasses.dataclass(frozen=True)
class GroupedGemmBwdDescriptor(GroupedGemmDescriptor):
    """Grouped-GEMM backward: dY, X, W, group_sizes -> dX, dW, (dB).

    Inherits the forward geometry so ``GroupedGemmPlan.tile_schedule()``
    applies unchanged; the distinct ``family`` keys separate plan/kernel
    cache rows and launch counters (DESIGN.md §11).
    """

    family = "grouped_gemm_bwd"

    @classmethod
    def from_forward(cls, desc: GroupedGemmDescriptor
                     ) -> "GroupedGemmBwdDescriptor":
        """Backward descriptor sharing a forward descriptor's geometry.

        The quant spec is deliberately dropped: quantization is a
        forward/inference axis (DESIGN.md §13) — backward walks run in
        the wide dtype on the saved wide residuals.  The mesh spec is
        dropped too: the distributed path runs the *local* grouped GEMM
        (whose VJP this descriptor keys) under ``shard_map``, so the
        backward geometry is always the meshless per-shard problem
        (DESIGN.md §14).
        """
        fields = dataclasses.asdict(desc)
        fields["quant"] = None  # asdict flattens QuantSpec to a dict anyway
        fields["mesh"] = None   # same for MeshSpec
        return cls(**fields)

    @property
    def flops(self) -> int:
        # dX = dY @ W^T and dW = X^T @ dY: two contractions of forward cost.
        return 2 * super().flops

    @property
    def in_bytes(self) -> int:
        isz = jnp.dtype(self.dtype).itemsize
        return (self.t * (self.k + self.n)
                + self.num_experts * self.k * self.n) * isz

    @property
    def out_bytes(self) -> int:
        isz = jnp.dtype(self.dtype).itemsize
        # dX in operand dtype; dW (and db when biased) staged in fp32.
        total = self.t * self.k * isz + self.num_experts * self.k * self.n * 4
        if self.epilogue in BIAS_EPILOGUES:
            total += self.num_experts * self.n * 4
        return total


@dataclasses.dataclass(frozen=True)
class SsdChunkBwdDescriptor(SsdChunkDescriptor):
    """SSD chunked-scan backward: reverse walk with carried (p,n) cotangent.

    Geometry matches the forward :class:`SsdChunkDescriptor` (scan form,
    ``chunks >= 1``); the distinct ``family`` gives backward plans their
    own cache/autotune/launch accounting (DESIGN.md §11).
    """

    family = "ssd_chunk_bwd"

    @classmethod
    def from_forward(cls, desc: SsdChunkDescriptor) -> "SsdChunkBwdDescriptor":
        """Backward descriptor sharing a forward descriptor's geometry."""
        return cls(**dataclasses.asdict(desc))

    @property
    def flops(self) -> int:
        # Each forward GEMM spawns two cotangent GEMMs in reverse.
        return 2 * super().flops

    @property
    def in_bytes(self) -> int:
        # Forward operands + dY/dSf cotangents + the saved per-chunk fp32
        # carried states the reverse walk consumes.
        extra = (self.cells * self.q * self.p
                 * jnp.dtype(self.dtype).itemsize          # dY
                 + 2 * self.groups * self.p * self.n * 4   # dSf + s0
                 + self.cells * self.p * self.n * 4)       # saved states
        return super().in_bytes + extra

    @property
    def out_bytes(self) -> int:
        isz = jnp.dtype(self.dtype).itemsize
        per_cell = (2 * self.q * self.n + self.q * self.q  # dc, db, dl
                    + self.q * self.p)                     # dx
        return (self.cells * per_cell * isz
                + self.cells * 2 * self.q * 4              # ddi/ddo, fp32
                + self.groups * self.p * self.n * 4)       # ds0


@dataclasses.dataclass(frozen=True)
class TransposeDescriptor(KernelDescriptor):
    """Blocked (batched) 2-D transpose: (..., rows, cols) -> (..., cols, rows).

    ``batch`` is a leading grid dimension of the generated kernel, not a
    ``vmap`` — a batched transpose is ONE launch (DESIGN.md §9).
    """

    family = "transpose"

    rows: int
    cols: int
    dtype: str = "float32"
    # leading batch dim shared by in/out; 0 => unbatched 2-D transpose
    batch: int = 0

    def __post_init__(self):
        if self.rows <= 0 or self.cols <= 0:
            raise ValueError(f"transpose dims must be positive, got {self}")

    @classmethod
    def from_operands(cls, x):
        batch = 0
        if x.ndim == 3:
            batch = x.shape[0]
        elif x.ndim != 2:
            raise ValueError(f"transpose operand must be rank 2 or 3, "
                             f"got {x.ndim}")
        rows, cols = x.shape[-2], x.shape[-1]
        return cls(rows=rows, cols=cols, dtype=canonical_dtype(x.dtype),
                   batch=batch)

    @property
    def flops(self) -> int:
        return 0  # pure data movement

    @property
    def in_bytes(self) -> int:
        nb = max(1, self.batch)
        return nb * self.rows * self.cols * jnp.dtype(self.dtype).itemsize

    @property
    def out_bytes(self) -> int:
        return self.in_bytes


# ---------------------------------------------------------------------------
# Cache-key round trip (DESIGN.md §15)
# ---------------------------------------------------------------------------

# Family name -> descriptor class, for rebuilding a descriptor from its
# engine cache key.  Kept next to the classes so adding a family here is
# part of adding the family.
_FAMILY_DESCRIPTORS = {
    cls.family: cls for cls in (
        GemmDescriptor, FlashDescriptor, FlashBwdDescriptor,
        FlashDecodeDescriptor, GroupedGemmDescriptor,
        GroupedGemmBwdDescriptor, SsdChunkDescriptor, SsdChunkBwdDescriptor,
        TransposeDescriptor)
}


def descriptor_from_cache_key(key) -> KernelDescriptor:
    """Rebuild the descriptor a ``cache_key()`` tuple names.

    ``cache_key()`` is ``(family,) + dataclasses.astuple(desc)``, with
    nested :class:`QuantSpec` / :class:`MeshSpec` recursed into plain
    tuples — so the key is fully invertible.  This is what lets the
    offline refit pipeline and the warm-start manifest reconstruct the
    exact descriptor population from TuningCache entry keys and recorded
    manifests (DESIGN.md §15).  Raises ``ValueError`` on an unknown
    family or a field-count mismatch (a key written by a different
    descriptor schema must not silently half-apply).
    """
    key = tuple(key)
    if not key:
        raise ValueError("empty cache key")
    family, values = key[0], key[1:]
    cls = _FAMILY_DESCRIPTORS.get(family)
    if cls is None:
        raise ValueError(f"unknown descriptor family {family!r}; "
                         f"known: {sorted(_FAMILY_DESCRIPTORS)}")
    fields = dataclasses.fields(cls)
    if len(values) != len(fields):
        raise ValueError(
            f"{family} cache key carries {len(values)} fields, the "
            f"descriptor schema has {len(fields)} — written by a "
            f"different version?")
    kwargs = {}
    for f, v in zip(fields, values):
        if v is not None:
            if f.name == "quant":
                v = QuantSpec(*v)
            elif f.name == "mesh":
                v = MeshSpec(*v)
            elif isinstance(v, list):
                v = tuple(v)
        kwargs[f.name] = v
    return cls(**kwargs)
