"""Encoder–decoder model (seamless-m4t backbone stub).

Encoder: bidirectional attention over precomputed audio-frame embeddings
(the modality frontend is a stub per the assignment).  Decoder: causal
self-attention + cross-attention into the encoder output, sharing the
block machinery of the decoder-only stack.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import common
from repro.models.attention import attention_apply, attention_init
from repro.models.blocks import stack_apply, stack_cache, stack_init
from repro.models.frontends import frontend_apply, frontend_init
from repro.models.mlp import mlp_apply, mlp_init
from repro.runtime.shardlib import shard_activation


def _encoder_block_init(rng, cfg):
    r1, r2 = common.split_rngs(rng, 2)
    return {
        "norm_attn": common.norm_init(cfg.norm_type, cfg.d_model),
        "attn": attention_init(r1, cfg),
        "norm_ff": common.norm_init(cfg.norm_type, cfg.d_model),
        "ff": mlp_init(r2, cfg),
    }


def _encoder_block_apply(params, cfg, x, positions):
    h = common.norm_apply(cfg.norm_type, params["norm_attn"], x, cfg.norm_eps)
    # bidirectional: kv_override = the sequence itself (no causal mask)
    y, _ = attention_apply(params["attn"], cfg, h, positions, kv_override=h)
    x = x + y
    h = common.norm_apply(cfg.norm_type, params["norm_ff"], x, cfg.norm_eps)
    x = x + mlp_apply(params["ff"], cfg, h)
    return shard_activation(x, (("pod", "data"), "model", None))


class EncoderDecoderModel:
    @staticmethod
    def init(rng, cfg):
        r_f, r_enc, r_dec, r_emb, r_norm_e, r_head = common.split_rngs(rng, 6)
        enc_rngs = common.split_rngs(r_enc, cfg.num_encoder_layers)

        def one(r):
            return _encoder_block_init(r, cfg)

        enc_stacked = jax.vmap(one)(jnp.stack(enc_rngs))
        return {
            "frontend": frontend_init(r_f, cfg),
            "encoder": enc_stacked,
            "enc_norm": common.norm_init(cfg.norm_type, cfg.d_model),
            "embed": common.embedding_init(r_emb, cfg.vocab_size, cfg.d_model),
            "decoder": stack_init(r_dec, cfg, cross=True),
            "final_norm": common.norm_init(cfg.norm_type, cfg.d_model),
            "lm_head": common.linear_init(r_head, cfg.d_model, cfg.vocab_size),
        }

    @staticmethod
    def encode(params, cfg, feats):
        """feats: (b, s_enc, modality_dim) -> (b, s_enc, d)."""
        x = frontend_apply(params["frontend"], cfg, feats)
        positions = jnp.arange(x.shape[1], dtype=jnp.int32)
        x = shard_activation(x, (("pod", "data"), "model", None))

        def body(h, blk_params):
            return _encoder_block_apply(blk_params, cfg, h, positions), None

        if cfg.remat:
            body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable,
                                  prevent_cse=False)
        x, _ = jax.lax.scan(body, x, params["encoder"])
        return common.norm_apply(cfg.norm_type, params["enc_norm"], x, cfg.norm_eps)

    @staticmethod
    def apply(params, cfg, tokens, feats=None, *, enc_out=None, positions=None,
              cache=None, logits_mode="all"):
        """Teacher-forced decode over ``tokens`` given encoder input.
        Returns (logits, new_cache, aux_loss, expert_counts), as
        ``LanguageModel.apply``."""
        dt = jnp.dtype(cfg.dtype)
        if enc_out is None:
            enc_out = EncoderDecoderModel.encode(params, cfg, feats)
        b, s = tokens.shape
        x = common.embed(params["embed"], tokens, dt)
        if positions is None:
            positions = jnp.arange(s, dtype=jnp.int32)
        x = shard_activation(x, (("pod", "data"), "model", None))
        x, new_cache, aux, counts = stack_apply(params["decoder"], cfg, x,
                                                positions, cache=cache,
                                                enc_out=enc_out)
        x = common.norm_apply(cfg.norm_type, params["final_norm"], x, cfg.norm_eps)
        if logits_mode == "last":
            x = x[:, -1:]
        w = common.cast_param(params["lm_head"]["w"], dt)
        from repro.core import matmul
        logits = matmul(x, w, out_dtype=jnp.dtype(cfg.logits_dtype))
        logits = shard_activation(logits, (("pod", "data"), "model", None))
        return logits, new_cache, aux, counts

    @staticmethod
    def init_cache(cfg, batch, capacity):
        return stack_cache(batch, cfg, capacity)
