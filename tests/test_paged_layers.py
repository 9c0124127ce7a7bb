"""The paged decode step's KV pools across the layer scan (DESIGN.md §12).

The continuous-batching decode step carries the stacked pools through
its layer scan, writes each new row in place at ``[layer, page,
offset]`` and lets ``flash_decode`` read its layer from the stack.  These
tests hold that formulation to the one it replaced, kept here as the
oracle: a scan whose steps each take one layer's pool out of the stack
and put it back.  Each layer's pool rows and the emitted tokens must
agree over several steps, with an inactive slot and with slots on
scattered pages, for wide and int8 pools, on the XLA path and the
interpreted kernel.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced_config
from repro.core import use
from repro.kernels.flash_attention import paged_decode_attention
from repro.models import LanguageModel, blocks, lm
from repro.models.attention import PagedKVCache, PageSpec
from repro.runtime.pages import init_serving_cache
from repro.runtime.steps import make_paged_serve_step

SLOTS, PAGES, P, MAX_BLOCKS = 3, 12, 4, 4
# Slot 0 and 1 on scattered pages, slot 2 inactive on a page of its own.
TABLES = np.array([[3, 7, 10, 0], [1, 5, 9, 11], [2, 0, 0, 0]], np.int32)
LENGTHS = np.array([5, 8, 3], np.int32)
ACTIVE = np.array([True, True, False])
STEPS = 4


def _per_layer_stack_apply(params, cfg, x, positions, *, cache=None,
                           enc_out=None):
    """The oracle: the layer scan with every cache leaf in its ``xs`` and
    ``ys``, so each step applies one layer with its own pool, sliced out
    of the stack and stacked back; remainder layers as in
    ``blocks.stack_apply``."""
    groups, rem = blocks.stack_layout(cfg)

    def body(carry, xs):
        h, aux = carry
        gp, gc = xs
        assert all(c.layer is None for c in gc.values()
                   if isinstance(c, PagedKVCache))
        h, nc, a = blocks._group_apply(gp, cfg, h, positions, gc, enc_out)
        return (h, blocks._add(aux, a)), nc

    (x, aux), stacked = jax.lax.scan(
        body, (x, blocks._no_aux(cfg)),
        (params["groups"], cache["groups"]))
    new_rem = []
    for i, kind in enumerate(rem):
        x, nc, a = blocks.block_apply(params["rem"][i], cfg, kind, x,
                                      positions, cache=cache["rem"][i])
        aux = blocks._add(aux, a)
        new_rem.append(nc)
    return x, {"groups": stacked, "rem": new_rem}, aux[0], aux[1]


def _filled_cache(cfg, spec, seed):
    """A serving cache whose pools hold distinct random values in every
    layer and page, with ``TABLES`` as every block table."""
    cache = init_serving_cache(cfg, SLOTS, spec)
    rng = np.random.default_rng(seed)

    def fill(x):
        if not isinstance(x, PagedKVCache):
            return x
        def rand(a, lo=-1.0, hi=1.0):
            vals = rng.uniform(lo, hi, a.shape)
            if a.dtype == jnp.int8:
                vals = np.round(vals * 127)
            return jnp.asarray(vals, a.dtype)
        scales = {}
        if x.k_scale is not None:
            scales = dict(k_scale=rand(x.k_scale, 0.001, 0.01),
                          v_scale=rand(x.v_scale, 0.001, 0.01))
        return x._replace(k=rand(x.k), v=rand(x.v),
                          tables=jnp.broadcast_to(jnp.asarray(TABLES),
                                                  x.tables.shape),
                          **scales)

    return jax.tree.map(fill, cache,
                        is_leaf=lambda x: isinstance(x, PagedKVCache))


def _serve(cfg, params, cache, steps):
    step = jax.jit(make_paged_serve_step(cfg))
    tok = jnp.asarray(np.arange(SLOTS, dtype=np.int32)[:, None] + 7)
    lengths, active = jnp.asarray(LENGTHS), jnp.asarray(ACTIVE)
    toks = []
    for _ in range(steps):
        tok, cache, _ = step(params, cache, tok, lengths, active)
        lengths = jnp.where(active, lengths + 1, lengths)
        toks.append(np.asarray(tok)[:, 0])
    return np.stack(toks), cache


def _paged_leaves(cache):
    """(path, PagedKVCache) of every paged leaf, stacked ones first."""
    out = []
    for key, c in (cache["groups"] or {}).items():
        if isinstance(c, PagedKVCache):
            out.append((f"groups.{key}", c))
    for i, r in enumerate(cache["rem"]):
        if isinstance(r, PagedKVCache):
            out.append((f"rem.{i}", r))
    return out


CASES = [
    # (arch, overrides): qwen3 scans three paged layers; the hybrid puts a
    # recurrent block beside each paged one in a scanned group and leaves
    # a paged remainder layer outside the scan.
    ("qwen3-0.6b", {"num_layers": 3}),
    ("recurrentgemma-9b", {"num_layers": 5, "block_pattern": ("attn", "rec")}),
]


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("kv", ["bf16", "int8"])
@pytest.mark.parametrize("arch,overrides", CASES)
def test_scan_carry_matches_per_layer(arch, overrides, kv, backend,
                                      monkeypatch):
    cfg = reduced_config(get_config(arch), dtype="bfloat16",
                         kv_cache_dtype="bfloat16", **overrides)
    spec = PageSpec(PAGES, P, MAX_BLOCKS,
                    kv_quant="int8" if kv == "int8" else None)
    params = LanguageModel.init(jax.random.PRNGKey(1), cfg)
    cache = _filled_cache(cfg, spec, seed=4)
    before = {path: c for path, c in _paged_leaves(cache)}

    with use(backend=backend):
        toks, got = _serve(cfg, params, cache, STEPS)
        monkeypatch.setattr(lm, "stack_apply", _per_layer_stack_apply)
        want_toks, want = _serve(cfg, params, cache, STEPS)

    np.testing.assert_array_equal(toks[:, ACTIVE], want_toks[:, ACTIVE])
    got_leaves, want_leaves = _paged_leaves(got), _paged_leaves(want)
    assert [p for p, _ in got_leaves] == [p for p, _ in want_leaves]
    assert got_leaves, "the case holds no paged layer"
    for (path, g), (_, w) in zip(got_leaves, want_leaves):
        assert g.layer is None and g.k.shape == before[path].k.shape
        for field in ("k", "v", "k_scale", "v_scale"):
            gf, wf = getattr(g, field), getattr(w, field)
            if wf is None:
                assert gf is None
                continue
            gf, wf = np.asarray(gf, np.float32), np.asarray(wf, np.float32)
            # every layer of a stack, row for row
            np.testing.assert_array_equal(gf, wf, err_msg=f"{path}.{field}")
        # The inactive slot's page is untouched; each active slot's new
        # rows were written in every layer.
        k0 = np.asarray(before[path].k, np.float32)
        kn = np.asarray(g.k, np.float32)
        np.testing.assert_array_equal(kn[..., TABLES[2, 0], :, :, :],
                                      k0[..., TABLES[2, 0], :, :, :])
        for s in np.flatnonzero(ACTIVE):
            for p in range(LENGTHS[s], LENGTHS[s] + STEPS):
                page, off = TABLES[s, p // P], p % P
                changed = np.any(kn[..., page, off, :, :]
                                 != k0[..., page, off, :, :], axis=(-2, -1))
                assert np.all(changed), (path, s, p)


@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_flash_decode_reads_layer_of_stack(kv):
    """The layer-indexed kernel on a stacked pool equals the single-layer
    call on that layer's pool, for every layer, the index traced."""
    rng = np.random.default_rng(11)
    L, S, pages, psize, maxb, h, hkv, hd = 3, 3, 10, 8, 3, 4, 2, 16
    q = jnp.asarray(rng.standard_normal((S, h, hd)), jnp.bfloat16)
    tables = jnp.asarray(rng.permutation(pages)[:S * maxb].reshape(S, maxb),
                         jnp.int32)
    lengths = jnp.asarray([17, 0, 9], jnp.int32)
    shape = (L, pages, psize, hkv, hd)
    if kv == "int8":
        k, v = (jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
                for _ in range(2))
        ks, vs = (jnp.asarray(rng.uniform(0.001, 0.01, shape[:3]),
                              jnp.float32) for _ in range(2))
    else:
        k, v = (jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
                for _ in range(2))
        ks = vs = None

    with use(backend="pallas"):
        stacked = jax.jit(
            lambda layer: paged_decode_attention(
                q, k, v, tables, lengths, k_scale=ks, v_scale=vs,
                layer=layer))
        for i in range(L):
            got = stacked(jnp.int32(i))
            want = paged_decode_attention(
                q, k[i], v[i], tables, lengths,
                k_scale=None if ks is None else ks[i],
                v_scale=None if vs is None else vs[i])
            np.testing.assert_array_equal(np.asarray(got, np.float32),
                                          np.asarray(want, np.float32),
                                          err_msg=f"layer {i}")
