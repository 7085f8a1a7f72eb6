"""Top-level model: init / forward / loss / decode — uniform over all 10 archs.

``[audio]``/``[vlm]`` modality frontends are STUBS per the assignment: callers pass
precomputed frame/patch embeddings (``inputs_embeds`` / ``img_embeds``); only the
transformer backbone is modelled.
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from .attention import precompute_cross_kv
from .layers import (
    apply_norm,
    chunked_cross_entropy,
    embed_tokens,
    init_embed,
    init_norm,
    init_unembed,
    softmax_cross_entropy,
    unembed,
)
from .transformer import (
    _draft_layer_slices,
    apply_block_decode,
    apply_stack_decode,
    apply_stack_train,
    apply_stack_verify,
    init_stack,
    init_stack_cache,
)


def _dtype(cfg: ModelConfig):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


class Model:
    """Functional model bound to a config (params are explicit pytrees)."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    # ------------------------------------------------------------------- params
    def init(self, key) -> dict:
        cfg = self.cfg
        dt = _dtype(cfg)
        k_embed, k_stack, k_un = jax.random.split(key, 3)
        params = {
            "embed": init_embed(k_embed, cfg, dt),
            "stack": init_stack(k_stack, cfg, dt),
            "final_norm": init_norm(cfg, jnp.float32),
        }
        un = init_unembed(k_un, cfg, dt)
        if un:
            params["unembed"] = un
        return params

    def param_shapes(self) -> dict:
        """Shape/dtype tree without allocation (dry-run / sharding planning)."""
        return jax.eval_shape(lambda: self.init(jax.random.PRNGKey(0)))

    # ------------------------------------------------------------------ forward
    def forward(self, params, tokens=None, *, inputs_embeds=None, img_embeds=None,
                impl: str = "auto"):
        """Full-sequence forward → fp32 logits (train and prefill)."""
        cfg = self.cfg
        x, aux = self.backbone(params, tokens, inputs_embeds=inputs_embeds,
                               img_embeds=img_embeds, impl=impl)
        logits = unembed(params.get("unembed"), params["embed"], x,
                         cfg.tie_embeddings, cfg.logit_softcap)
        return logits, aux

    def loss(self, params, batch, *, impl: str = "auto", ce_chunk: int = 0):
        if ce_chunk:
            x, aux = self.backbone(
                params, batch.get("tokens"),
                inputs_embeds=batch.get("inputs_embeds"),
                img_embeds=batch.get("img_embeds"), impl=impl)
            cfg = self.cfg

            def unembed_fn(xc):
                return unembed(params.get("unembed"), params["embed"], xc,
                               cfg.tie_embeddings, cfg.logit_softcap)

            loss = chunked_cross_entropy(x, batch["labels"], unembed_fn,
                                         ce_chunk)
            return loss, aux
        logits, aux = self.forward(
            params, batch.get("tokens"),
            inputs_embeds=batch.get("inputs_embeds"),
            img_embeds=batch.get("img_embeds"), impl=impl)
        loss = softmax_cross_entropy(logits, batch["labels"],
                                     batch.get("loss_mask"))
        return loss, aux

    def backbone(self, params, tokens=None, *, inputs_embeds=None,
                 img_embeds=None, impl: str = "auto"):
        """Forward up to (but excluding) the unembedding (for chunked CE)."""
        cfg = self.cfg
        dt = _dtype(cfg)
        if inputs_embeds is not None:
            x = inputs_embeds.astype(dt)
        else:
            x = embed_tokens(params["embed"], tokens, dt)
        if cfg.embed_scale != 1.0:
            x = x * jnp.asarray(cfg.embed_scale, dt)
        B, S = x.shape[:2]
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        if img_embeds is not None:
            img_embeds = img_embeds.astype(dt)
        x, drop = apply_stack_train(params["stack"], x, positions, cfg,
                                    img_embeds=img_embeds, impl=impl)
        x = apply_norm(params["final_norm"], x, cfg.norm)
        return x, {"dropped_fraction": drop}

    # ------------------------------------------------------------------- decode
    def init_cache(self, batch: int, max_len: int) -> dict:
        return init_stack_cache(batch, self.cfg, max_len, _dtype(self.cfg))

    def cache_shapes(self, batch: int, max_len: int) -> dict:
        return jax.eval_shape(lambda: self.init_cache(batch, max_len))

    def decode_step(self, params, token, cache, pos, *, routed: bool = False):
        """One new token against an existing cache (serve_step for decode cells).

        token: (B, 1) int32; pos: scalar int32 (global position). Returns
        (fp32 logits (B, 1, V), new cache), and with ``routed`` a third
        output: the rows routed to each held expert, summed over the layers
        ((experts_held,) int32; MoE only).
        """
        cfg = self.cfg
        dt = _dtype(cfg)
        x = embed_tokens(params["embed"], token, dt)
        if cfg.embed_scale != 1.0:
            x = x * jnp.asarray(cfg.embed_scale, dt)
        x, new_cache, pairs = apply_stack_decode(params["stack"], x, cache,
                                                 pos, cfg)
        x = apply_norm(params["final_norm"], x, cfg.norm)
        logits = unembed(params.get("unembed"), params["embed"], x,
                         cfg.tie_embeddings, cfg.logit_softcap)
        if routed:
            return logits, new_cache, pairs
        return logits, new_cache

    def supports_speculation(self) -> bool:
        """Speculative decode windows need every cache write to be positional
        and idempotent, so a rejected draft's stale entries are overwritten
        before anything reads them: pure full-attention stacks only (ring
        buffers and recurrent states advance destructively), and no MoE (not
        covered yet: the speculative window returns no routed-pair counters,
        and no test ties its MoE verify rows to sequential decode)."""
        cfg = self.cfg
        return (all(b == "attn" for b in cfg.pattern_layers)
                and not cfg.is_moe)

    def verify_step(self, params, tokens, cache, pos):
        """T-token decode ("speculative verify") against an existing cache.

        tokens: (B, T) int32 at positions ``pos .. pos+T-1``; pos: scalar
        int32. Returns (fp32 logits (B, T, V), new cache). Row ``t`` computes
        exactly :meth:`decode_step` at position ``pos+t`` (the verify stack
        mirrors the decode stack per token row), so accepted tokens — and the
        cache entries they leave behind — are bit-equal to sequential decode.
        """
        cfg = self.cfg
        dt = _dtype(cfg)
        x = embed_tokens(params["embed"], tokens, dt)
        if cfg.embed_scale != 1.0:
            x = x * jnp.asarray(cfg.embed_scale, dt)
        x, new_cache = apply_stack_verify(params["stack"], x, cache, pos, cfg)
        x = apply_norm(params["final_norm"], x, cfg.norm)
        logits = unembed(params.get("unembed"), params["embed"], x,
                         cfg.tie_embeddings, cfg.logit_softcap)
        return logits, new_cache

    def draft_chain(self, params, token, cache, pos, *, draft_layers: int,
                    draft_len: int, override=None, n_forced=None):
        """``draft_len`` chained shallow-exit draft steps in ONE call.

        The chain slices the drafter's layer params/caches out of the
        period-stacked trees once and writes them back once, so the stacked-
        leaf copies (the dominant drafter cost at small scale) don't scale
        with draft depth. ``override``/``n_forced`` force-feed pending prompt
        tokens through the chain: proposal ``d+1`` is replaced by
        ``override[d]`` while ``d+1 < n_forced`` (the speculative window's
        verify-width prompt feed).

        token: (B, 1) int32 at position ``pos``. Returns
        (proposals (B, draft_len) int32, new cache).
        """
        cfg = self.cfg
        dt = _dtype(cfg)
        work = {"periods": dict(cache["periods"]), "rest": list(cache["rest"])}
        layers = _draft_layer_slices(params["stack"], work, cfg, draft_layers)
        local = [pc for _, pc, _, _ in layers]
        tok = token
        outs = []
        for d in range(draft_len):
            x = embed_tokens(params["embed"], tok, dt)
            if cfg.embed_scale != 1.0:
                x = x * jnp.asarray(cfg.embed_scale, dt)
            for i, (pp, _, btype, _) in enumerate(layers):
                x, local[i], _ = apply_block_decode(pp, x, local[i], pos + d,
                                                    cfg, btype)
            x = apply_norm(params["final_norm"], x, cfg.norm)
            logits = unembed(params.get("unembed"), params["embed"], x,
                             cfg.tie_embeddings, cfg.logit_softcap)
            nxt = jnp.argmax(logits[:, -1, :], axis=-1).astype(
                jnp.int32)[:, None]
            if override is not None:
                nxt = jnp.where(d + 1 < n_forced, override[d:d + 1][None, :],
                                nxt)
            outs.append(nxt)
            tok = nxt
        for i, (_, _, _, wb) in enumerate(layers):
            wb(work, local[i])
        return jnp.concatenate(outs, axis=1), work

    def prefill(self, params, tokens, *, img_embeds=None, impl: str = "auto"):
        """Prefill returning logits only (the prefill_32k cells lower this).

        Cache-producing prefill for interactive serving is
        ``launch.steps.make_cache_prefill`` (decode-loop based; exact,
        small-scale), driven by the ``repro.serve`` subsystem.
        """
        return self.forward(params, tokens, img_embeds=img_embeds, impl=impl)


def build_model(cfg: ModelConfig) -> Model:
    return Model(cfg)
