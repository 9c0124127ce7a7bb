"""Decoder-only language model (with optional modality prefix).

API (pure functions over nested-dict pytrees):

  * ``LanguageModel.init(rng, cfg) -> params``
  * ``LanguageModel.apply(params, cfg, tokens, ...) ->
    (logits, cache, aux, expert_counts)``
  * ``LanguageModel.init_cache(cfg, batch, capacity) -> cache``

Decode is ``apply`` with a 1-token input and a cache; caches for "local"
blocks are ring buffers of size ``attn_window`` and for "rec"/"ssm" blocks
are O(1) states — so 500k-context decode carries no 500k-sized buffers for
sub-quadratic architectures.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import common
from repro.models.blocks import stack_apply, stack_cache, stack_init
from repro.models.frontends import frontend_apply, frontend_init
from repro.runtime.shardlib import shard_activation


class LanguageModel:
    @staticmethod
    def init(rng, cfg):
        r_embed, r_stack, r_norm, r_head, r_front = common.split_rngs(rng, 5)
        params = {
            "embed": common.embedding_init(r_embed, cfg.vocab_size, cfg.d_model),
            "blocks": stack_init(r_stack, cfg),
            "final_norm": common.norm_init(cfg.norm_type, cfg.d_model),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = common.linear_init(r_head, cfg.d_model,
                                                   cfg.vocab_size)
        if cfg.modality is not None:
            params["frontend"] = frontend_init(r_front, cfg)
        return params

    @staticmethod
    def apply(params, cfg, tokens, *, positions=None, cache=None,
              modality_feats=None, logits_mode="all"):
        """tokens: (b, s) int32.  modality_feats: (b, n_mod, modality_dim)
        prepended before the text tokens (positions account for the
        prefix).  ``logits_mode="last"`` unembeds only the final position
        (prefill: skips a (b,s,V)-sized matmul + HBM round-trip).
        Returns (logits, new_cache, aux_loss, expert_counts), the counts
        the MoE layers' int32 ``(rows routed to held experts, held
        experts hit)`` (zeros without experts)."""
        dt = jnp.dtype(cfg.dtype)
        b, s = tokens.shape
        x = common.embed(params["embed"], tokens, dt)
        if cfg.embed_scale:
            x = x * jnp.asarray(cfg.d_model ** 0.5, dt)
        if cfg.embedding_multiplier != 1.0:
            x = x * jnp.asarray(cfg.embedding_multiplier, dt)

        n_mod = 0
        if modality_feats is not None:
            prefix = frontend_apply(params["frontend"], cfg, modality_feats)
            n_mod = prefix.shape[1]
            x = jnp.concatenate([prefix, x], axis=1)

        if positions is None:
            positions = jnp.arange(s + n_mod, dtype=jnp.int32)
        x = shard_activation(x, (("pod", "data"), "model", None))

        x, new_cache, aux, counts = stack_apply(params["blocks"], cfg, x,
                                                positions, cache=cache)
        x = common.norm_apply(cfg.norm_type, params["final_norm"], x,
                              cfg.norm_eps)
        if logits_mode == "last":
            x = x[:, -1:]
        ldt = jnp.dtype(cfg.logits_dtype)
        if cfg.tie_embeddings:
            logits = common.unembed(params["embed"], x, dt, out_dtype=ldt)
        else:
            w = common.cast_param(params["lm_head"]["w"], dt)
            from repro.core import matmul
            logits = matmul(x, w, out_dtype=ldt)
        if cfg.logits_scaling != 1.0:
            logits = logits / jnp.asarray(cfg.logits_scaling, logits.dtype)
        if cfg.final_logit_softcap:
            cap = cfg.final_logit_softcap
            logits = jnp.tanh(logits / cap) * cap
        logits = shard_activation(logits, (("pod", "data"), "model", None))
        return logits, new_cache, aux, counts

    @staticmethod
    def init_cache(cfg, batch, capacity, paged=None):
        """``paged`` (a :class:`repro.models.attention.PageSpec`) builds
        the continuous-batching serving cache: "attn" blocks become paged
        pools + block tables, everything else stays slot-major dense
        (DESIGN.md §12)."""
        return stack_cache(batch, cfg, capacity, paged)
