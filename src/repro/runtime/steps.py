"""Step builders: train_step / prefill_step / serve_step for any arch.

These are the functions the launcher jits with explicit in/out shardings
and the dry-run lowers against ShapeDtypeStructs.  All model-family
branching (dec-only vs enc-dec vs modality prefix) is resolved here, at
trace time, from the config.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.models import EncoderDecoderModel, LanguageModel
from repro.models.losses import softmax_cross_entropy

AUX_LOSS_WEIGHT = 0.01
Z_LOSS = 1e-4


def model_for(cfg):
    return EncoderDecoderModel if cfg.encoder_decoder else LanguageModel


def forward(cfg, params, batch: Dict[str, Any], *, cache=None, positions=None,
            logits_mode="all"):
    """``(logits, new_cache, aux_loss)``: :func:`forward_counted` without
    the expert counts."""
    return forward_counted(cfg, params, batch, cache=cache,
                           positions=positions, logits_mode=logits_mode)[:3]


def forward_counted(cfg, params, batch: Dict[str, Any], *, cache=None,
                    positions=None, logits_mode="all"):
    """``(logits, new_cache, aux_loss, expert_counts)``: the counts are
    the MoE layers' int32 ``(rows routed to held experts, held experts
    hit)``, zeros without experts."""
    if cfg.encoder_decoder:
        return EncoderDecoderModel.apply(
            params, cfg, batch["tokens"], feats=batch.get("modality_feats"),
            enc_out=batch.get("enc_out"), positions=positions, cache=cache,
            logits_mode=logits_mode)
    return LanguageModel.apply(
        params, cfg, batch["tokens"], positions=positions, cache=cache,
        modality_feats=batch.get("modality_feats"), logits_mode=logits_mode)


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

def make_loss_fn(cfg):
    def loss_fn(params, batch):
        logits, _, aux = forward(cfg, params, batch)
        labels = batch["labels"]
        if cfg.modality == "vision":
            # loss over the text positions only (prefix carries no labels)
            logits = logits[:, -labels.shape[1]:]
        loss, metrics = softmax_cross_entropy(logits, labels, z_loss=Z_LOSS)
        total = loss + AUX_LOSS_WEIGHT * aux
        metrics = dict(metrics, aux_loss=aux, loss=total)
        return total, metrics

    return loss_fn


def make_train_step(cfg, optimizer, *, microbatches: int = 1,
                    grad_compress: bool = False):
    """Build the jittable train step.

    ``microbatches`` > 1 splits the global batch along the batch dim and
    accumulates gradients across a ``lax.scan`` — activation memory scales
    with 1/microbatches while the global batch (and the numerics, up to
    fp32 grad-sum order) is preserved.  ``grad_compress`` applies int8
    error-feedback quantization to the accumulated gradient (simulating
    the compressed cross-pod wire format; see repro.optim.compression).
    """
    loss_fn = make_loss_fn(cfg)

    def grads_of(params, batch):
        return jax.value_and_grad(loss_fn, has_aux=True)(params, batch)

    def train_step(params, opt_state, batch, step):
        if microbatches == 1:
            (_, metrics), grads = grads_of(params, batch)
        else:
            def split(x):
                b = x.shape[0]
                assert b % microbatches == 0, (b, microbatches)
                return x.reshape(microbatches, b // microbatches, *x.shape[1:])

            micro = jax.tree.map(split, batch)

            def body(acc, mb):
                (_, metrics), grads = grads_of(params, mb)
                # bf16 accumulation: halves the resident grad buffer (the
                # Megatron bf16-grad convention; loss scale is 1 in bf16).
                acc = jax.tree.map(
                    lambda a, g: a + g.astype(jnp.bfloat16), acc, grads)
                return acc, metrics

            zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.bfloat16),
                                 params)
            grads, metrics_stack = jax.lax.scan(body, zeros, micro)
            grads = jax.tree.map(lambda g: g / microbatches, grads)
            metrics = jax.tree.map(lambda m: m[-1], metrics_stack)

        if grad_compress:
            from repro.optim.compression import error_feedback_compress
            # residual is carried in opt_state["ef_residual"] when enabled
            res = opt_state.get("ef_residual") if isinstance(opt_state, dict) \
                else None
            grads, new_res = error_feedback_compress(grads, res)
        new_params, new_opt, opt_metrics = optimizer.update(
            grads, opt_state if not grad_compress else
            {k: v for k, v in opt_state.items() if k != "ef_residual"},
            params, step)
        if grad_compress:
            new_opt = dict(new_opt, ef_residual=new_res)
        return new_params, new_opt, {**metrics, **opt_metrics}

    return train_step


# ---------------------------------------------------------------------------
# inference
# ---------------------------------------------------------------------------

def make_prefill_step(cfg, capacity: int):
    """Prefill: forward the prompt, return last-position logits, cache
    and expert counts (:func:`forward_counted`)."""
    model = model_for(cfg)

    def prefill_step(params, batch):
        b = batch["tokens"].shape[0]
        cache = model.init_cache(cfg, b, capacity)
        # unembed only the last position: skips a (b, s, V) matmul + its
        # HBM round-trip (EXPERIMENTS.md §Perf, prefill iteration 1)
        logits, cache, _, counts = forward_counted(
            cfg, params, batch, cache=cache, logits_mode="last")
        return logits[:, -1], cache, counts

    return prefill_step


def make_serve_step(cfg):
    """One decode step:
    (params, cache, tokens(b,1), pos) -> (logits, cache, pos + 1).

    ``pos`` is carried *through* the jitted step (returned incremented)
    so decode loops never rebuild the position scalar host-side each
    iteration — rebuilding forced a host->device transfer per token.
    """

    def serve_step(params, cache, tokens, pos, enc_out=None):
        batch = {"tokens": tokens}
        if enc_out is not None:
            batch["enc_out"] = enc_out
        positions = pos[None] if pos.ndim == 0 else pos
        logits, new_cache, _ = forward(cfg, params, batch, cache=cache,
                                       positions=positions)
        return logits[:, -1], new_cache, pos + 1

    return serve_step


# ---------------------------------------------------------------------------
# continuous batching (DESIGN.md §12)
# ---------------------------------------------------------------------------

def _where_slot(active, new, old, axis: int):
    shp = [1] * new.ndim
    shp[axis] = active.shape[0]
    return jnp.where(active.reshape(shp), new, old)


def _merge_inactive(new_cache, old_cache, active):
    """Keep state rows of inactive slots from the previous step.

    Inactive slots run through the forward with position -1: their
    PagedKVCache scatters are already dropped in-kernel (shared pool —
    nothing to merge), but ring/recurrent/ssm rows compute garbage
    updates that must be masked back to the old state.  Grouped leaves
    carry the stacked-layer dim first (slot axis 1); "rem" leaves are
    slot-major (axis 0)."""
    from repro.models.attention import PagedKVCache

    def merge(n, o, axis):
        def f(nl, ol):
            if isinstance(nl, PagedKVCache):
                return nl
            return _where_slot(active, nl, ol, axis)

        return jax.tree.map(f, n, o,
                            is_leaf=lambda x: isinstance(x, PagedKVCache))

    out = {"groups": None, "rem": []}
    if new_cache["groups"] is not None:
        out["groups"] = merge(new_cache["groups"], old_cache["groups"], 1)
    out["rem"] = [merge(n, o, 0)
                  for n, o in zip(new_cache["rem"], old_cache["rem"])]
    return out


def repeat_rows(cache, n: int):
    """``cache`` with each batch row repeated ``n`` times in place (row
    ``i`` becomes rows ``i*n .. i*n+n-1``); the axes as in
    :func:`_merge_inactive`."""
    def rep(tree, axis):
        return jax.tree.map(lambda x: jnp.repeat(x, n, axis=axis), tree)

    groups = cache["groups"]
    return {"groups": None if groups is None else rep(groups, 1),
            "rem": [rep(r, 0) for r in cache["rem"]]}


def make_paged_serve_step(cfg):
    """One continuous-batching decode step over the paged serving cache.

    (params, cache, tokens(S,1), lengths(S,), active(S,)) ->
    (next_tokens(S,1), cache, expert_counts(2,)) — greedy argmax decode;
    inactive slots are frozen (state merged back, token row is garbage
    the scheduler ignores).  The signature is shape-stable in everything
    but the cache pytree, so the whole churning batch re-enters ONE
    compiled step; batch composition changes only flow through the block
    tables / lengths *values*, which the scheduler keeps on the host.
    The expert counts (:func:`forward_counted`) are of the active slots'
    rows only: inactive slots route to no expert.
    """

    def paged_serve_step(params, cache, tokens, lengths, active):
        positions = jnp.where(active, lengths, -1).astype(jnp.int32)[:, None]
        logits, new_cache, _, counts = forward_counted(
            cfg, params, {"tokens": tokens}, cache=cache,
            positions=positions)
        new_cache = _merge_inactive(new_cache, cache, active)
        tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
        return tok[:, None], new_cache, counts

    return paged_serve_step


# ---------------------------------------------------------------------------
# shape-only helpers for the dry-run
# ---------------------------------------------------------------------------

def param_shapes(cfg, rng=None):
    model = model_for(cfg)
    rng = jax.random.PRNGKey(0) if rng is None else rng
    return jax.eval_shape(functools.partial(model.init, cfg=cfg), rng)


def cache_shapes(cfg, batch: int, capacity: int):
    model = model_for(cfg)
    return jax.eval_shape(
        functools.partial(model.init_cache, cfg, batch, capacity))


def opt_state_shapes(cfg, optimizer, params_shapes):
    return jax.eval_shape(optimizer.init, params_shapes)
