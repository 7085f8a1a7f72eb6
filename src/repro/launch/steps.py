"""Step factories: jitted train / prefill / decode steps with the paper's in-band
error channel integrated (every step returns ``(outputs, metrics, error_word)``),
plus ShapeDtypeStruct input specs and shardings for every (arch × shape) cell.
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from ..configs.base import ModelConfig, ShapeConfig
from ..core.detect import ProbeConfig, loss_probe, state_probe, step_probe
from ..core.errors import ErrorCode
from ..core.faults import inject_batch, inject_grads, inject_loss
from ..models import build_model
from ..optim import AdamWConfig, adamw_update, init_opt_state, reset_moments
from ..sharding import (
    batch_shardings,
    cache_shardings,
    moment_shardings,
    param_shardings,
)


# ----------------------------------------------------------------- perf options
from dataclasses import dataclass as _dataclass


@_dataclass(frozen=True)
class PerfOptions:
    """Beyond-paper performance levers (see EXPERIMENTS.md §Perf).

    microbatch      — gradient accumulation over k microbatches (scan): activation
                      memory ÷ k at the cost of one grads-sized fp32 accumulator.
    ce_chunk        — chunked cross-entropy: never materialise (B,S,V) logits.
    seq_shard       — sequence-parallel residual stream: constrain activations to
                      P(dp, "model", None) between blocks so GSPMD lowers the
                      Megatron all-reduces to reduce-scatter + all-gather.
    cache_seq_model — decode KV caches sharded on the *capacity* dim over "model"
                      (scores stay sequence-sharded; softmax/psum exchanges tiny
                      (B,H) statistics instead of (B,H,T) score tensors).
    probes          — the in-band device channel on/off (off only for overhead
                      measurement — never in production).
    window          — decode-window size K for serving: scan K fused slot-decode
                      steps fully on device with deferred fault detection
                      (``make_decode_window``); 0 = per-token decode.
    donate          — donate caches/slot state to the decode window so XLA
                      updates them in place (no per-window cache copy).
    overlap         — fuse admission/LFLR prefill into the decode windows
                      (``make_prefill_decode_window``): joining or recovering
                      sequences advance their cache by a prompt chunk *inside*
                      the window scan, so prefill never stalls the token
                      stream; ignored when ``window == 0``.
    page            — paged KV pool page size for serving (``launch.paging``):
                      full-attention caches become a shared page pool addressed
                      through a per-slot page table, so long prompts and short
                      chats share HBM; 0 = one contiguous block per slot.
    speculate       — speculative decode windows (``make_speculative_decode_
                      window``): each window step drafts ``draft_len`` tokens
                      with a shallow-exit self-draft over the first
                      ``draft_layers`` layers, then verifies all drafts in one
                      batched full-model forward — up to ``draft_len + 1``
                      tokens per full-model step, token-bit-exact vs the plain
                      window engine; rejected drafts are attributed in-band
                      via ``ErrorCode.DRAFT_REJECT``. Requires ``window > 0``
                      and a pure full-attention architecture.
    draft_len       — tokens proposed per speculative window step (D).
    draft_layers    — layers of the shallow-exit drafter.
    """

    microbatch: int = 0
    ce_chunk: int = 0
    seq_shard: bool = False
    cache_seq_model: bool = False
    probes: bool = True
    ep_constraint: bool = False   # MoE dispatch buffers constrained E-over-model
    window: int = 0
    donate: bool = True
    overlap: bool = True
    page: int = 0
    speculate: bool = False
    draft_len: int = 3
    draft_layers: int = 1

    @classmethod
    def parse(cls, spec: str) -> "PerfOptions":
        """'mb=8,ce=2048,sp=1,cacheseq=1,probes=0,ep=1,window=8,donate=1,
        overlap=1,page=16,spec=1,dlen=3,dlayers=1' → PerfOptions."""
        kw: dict = {}
        for part in (spec or "").split(","):
            if not part:
                continue
            k, v = part.split("=")
            k = {"mb": "microbatch", "ce": "ce_chunk", "sp": "seq_shard",
                 "cacheseq": "cache_seq_model", "probes": "probes",
                 "ep": "ep_constraint", "win": "window", "window": "window",
                 "donate": "donate", "overlap": "overlap",
                 "page": "page", "spec": "speculate", "speculate": "speculate",
                 "dlen": "draft_len", "draft_len": "draft_len",
                 "dlayers": "draft_layers", "draft_layers": "draft_layers"}[k]
            kw[k] = bool(int(v)) if k in ("seq_shard", "cache_seq_model",
                                          "probes", "ep_constraint",
                                          "donate", "overlap",
                                          "speculate") else int(v)
        return cls(**kw)


BASELINE = PerfOptions()

# Dry-run cost-variant compiles set this so the microbatch scan is unrolled
# (cost_analysis counts while bodies once; see dryrun._corrected_costs).
MB_UNROLL = False


# -------------------------------------------------------------------- factories
def make_train_step(cfg: ModelConfig, opt_cfg: AdamWConfig | None = None,
                    probe_cfg: ProbeConfig | None = None, *, impl: str = "auto",
                    perf: PerfOptions = BASELINE):
    """(state, batch, inject) → (state', metrics, error_word).

    The error word is the in-band device channel (DESIGN.md §2): probes over loss,
    the full gradient stream, input tokens and the MoE router are OR-combined into
    one uint32 that the host's DeviceFuture converts into the paper's exceptions.
    """
    model = build_model(cfg)
    opt_cfg = opt_cfg or AdamWConfig()
    probe_cfg = probe_cfg or ProbeConfig()

    from ..models import transformer as _tf

    def _loss_and_grads(params, batch, tokens_inj):
        def loss_fn(p):
            b = dict(batch)
            if tokens_inj is not None:
                b["tokens"] = tokens_inj
            loss, aux = model.loss(p, b, impl=impl, ce_chunk=perf.ce_chunk)
            return loss, aux

        return jax.value_and_grad(loss_fn, has_aux=True)(params)

    def train_step(state, batch, inject):
        if True:
            tokens = batch.get("tokens")
            tokens_inj = (inject_batch(tokens, inject)
                          if tokens is not None else None)
            if perf.microbatch > 1:
                k = perf.microbatch

                def slice_mb(x, i):
                    B = x.shape[0]
                    return jax.lax.dynamic_slice_in_dim(x, i * (B // k),
                                                        B // k, 0)

                def body(carry, i):
                    g_acc, l_acc, d_acc = carry
                    b_i = {kk: slice_mb(v, i) for kk, v in batch.items()}
                    t_i = slice_mb(tokens_inj, i) if tokens_inj is not None else None
                    (loss, aux), grads = _loss_and_grads(state["params"], b_i,
                                                         t_i)
                    g_acc = jax.tree_util.tree_map(
                        lambda a, g: a + g.astype(jnp.float32) / k, g_acc,
                        grads)
                    return (g_acc, l_acc + loss / k,
                            d_acc + aux["dropped_fraction"] / k), None

                g0 = jax.tree_util.tree_map(
                    lambda p: jnp.zeros(p.shape, jnp.float32),
                    state["params"])
                import repro.launch.steps as _steps_mod
                (grads, loss, dropped), _ = jax.lax.scan(
                    body, (g0, jnp.float32(0), jnp.float32(0)),
                    jnp.arange(k),
                    unroll=True if _steps_mod.MB_UNROLL else 1)
                aux = {"dropped_fraction": dropped}
            else:
                (loss, aux), grads = _loss_and_grads(state["params"], batch,
                                                     tokens_inj)
            loss = inject_loss(loss, inject)
            grads = inject_grads(grads, inject)
            if perf.probes:
                word = step_probe(
                    loss, grads,
                    tokens=tokens_inj,
                    vocab_size=cfg.vocab_size if tokens is not None else None,
                    router_dropped=(aux["dropped_fraction"]
                                    if cfg.is_moe else None),
                    cfg=probe_cfg)
            else:
                word = jnp.uint32(0)
            new_params, new_opt, stats = adamw_update(
                opt_cfg, state["params"], grads, state["opt"], state["step"],
                lr_scale=state["lr_scale"])
            new_state = {"params": new_params, "opt": new_opt,
                         "step": state["step"] + 1,
                         "lr_scale": state["lr_scale"]}
            metrics = {"loss": loss, "grad_norm": stats["grad_norm"],
                       "lr": stats["lr"],
                       "dropped_fraction": aux["dropped_fraction"]}
            return new_state, metrics, word

    return train_step


def make_prefill_step(cfg: ModelConfig, probe_cfg: ProbeConfig | None = None, *,
                      impl: str = "auto"):
    model = build_model(cfg)
    probe_cfg = probe_cfg or ProbeConfig()

    def prefill_step(params, batch):
        logits, aux = model.forward(
            params, batch.get("tokens"),
            inputs_embeds=batch.get("inputs_embeds"),
            img_embeds=batch.get("img_embeds"), impl=impl)
        # serve-side probe: non-finite logits ⇒ NONFINITE_LOSS-class soft fault
        word = loss_probe(jnp.max(jnp.abs(logits)),
                          ProbeConfig(loss_divergence_threshold=jnp.inf))
        return logits, word

    return prefill_step


def make_decode_step(cfg: ModelConfig, probe_cfg: ProbeConfig | None = None,
                     *, routed: bool = False):
    """``(params, cache, token, pos) → (logits, new cache, word)``; with
    ``routed`` (MoE) a fourth output, the rows routed to each held expert
    (``Model.decode_step``)."""
    model = build_model(cfg)
    probe_cfg = probe_cfg or ProbeConfig()

    def decode_step(params, cache, token, pos):
        logits, new_cache, *pairs = model.decode_step(params, token, cache,
                                                      pos, routed=routed)
        # probe recurrent states only (KV re-probing would double memory traffic)
        words = [loss_probe(jnp.max(jnp.abs(logits)),
                            ProbeConfig(loss_divergence_threshold=jnp.inf))]
        rec = _recurrent_states(new_cache)
        if rec:
            words.append(state_probe(rec, probe_cfg))
        word = functools.reduce(lambda a, b: a | b, words)
        return (logits, new_cache, word, *pairs)

    return decode_step


def make_slot_decode_step(cfg: ModelConfig, probe_cfg: ProbeConfig | None = None,
                          *, routed: bool = False):
    """Per-slot decode for continuous batching (``repro.serve``).

    vmap of the single-sequence decode step over a leading *slot* axis, so every
    slot carries its own absolute position — the shape continuous batching
    needs, since slots join and leave the batch at different offsets:

      params                      shared across slots (in_axes=None)
      caches  pytree, leaves (S, ...)  stack of per-sequence (batch=1) caches
      tokens  (S, 1, 1) int32
      pos     (S,) int32               per-slot absolute position

    Returns ``(logits (S, 1, 1, V), new caches, error words (S,))``. The word
    is *per slot* (slots are independent under vmap), which is what makes
    per-sequence LFLR possible: the serve replica runs the word vector through
    the paper's enumeration algorithm (``core/device_channel.py``) so the
    resulting ``PropagatedError`` carries exact ``(slot, code)`` pairs instead
    of one blurred word for the whole batch.

    The per-slot body IS ``make_decode_step(cfg)`` — sharing it is what makes
    the serving LFLR recompute (prefill via the scalar decode step) reproduce
    the batched trajectory exactly.
    """
    return jax.vmap(make_decode_step(cfg, probe_cfg, routed=routed),
                    in_axes=(None, 0, 0, 0))


def _paged_slot_step(slot_step, paged):
    """Wrap the vmapped slot-decode step with page-table addressing.

    ``hybrid`` is the paged cache tree (pools + dense stacks); ``table`` the
    ``(S, max_pages)`` page table. Gather builds each slot's contiguous view
    (unmapped pages read as zeros — bit-identical to a fresh contiguous
    cache), the unchanged slot step runs on the views, and scatter writes
    them back through the table (unmapped pages dropped, so a lane that owns
    no pages writes nowhere). The in-band page probe ORs ``PAGE_FAULT`` into
    the slot's word iff the position being written is unmapped.
    """

    def step(params, hybrid, tokens, pos, table):
        views = paged.gather(hybrid, table)
        logits, views, words, *pairs = slot_step(params, views, tokens, pos)
        hybrid = paged.scatter(hybrid, views, table)
        return (logits, hybrid, words | paged.probe(table, pos), *pairs)

    return step


# ------------------------------------------------------- tensor parallelism
#: the serving-TP mesh axis name (matches the training rules in
#: ``repro.sharding.rules`` so one mesh can serve both).
TP_AXIS = "model"


@_dataclass(frozen=True)
class TPContext:
    """Everything a window factory needs to shard itself over a "model" axis.

    ``param_specs``/``cache_specs`` are PartitionSpec pytrees describing how
    the params / serve-cache (or hybrid pool) leaves are STORED across the
    mesh (``repro.sharding.rules.param_specs`` / ``tp_storage_specs``).
    Compute stays replicated: the TP window program all-gathers every sharded
    leaf back to its full value before the unchanged window body runs — see
    :func:`_tp_window`.
    """

    mesh: Any
    param_specs: Any
    cache_specs: Any

    @property
    def size(self) -> int:
        return int(self.mesh.shape[TP_AXIS])


def _tp_gather(x, spec):
    """All-gather a storage-sharded leaf back to its full value (``tiled``
    keeps element order, so the gathered tensor is bit-equal to the
    single-device original)."""
    for i, ax in enumerate(spec):
        if ax == TP_AXIS:
            return jax.lax.all_gather(x, TP_AXIS, axis=i, tiled=True)
    return x


def _tp_slice(x, spec, size: int):
    """Inverse of :func:`_tp_gather`: slice this shard's block back out of a
    full leaf before it leaves the shard_map program."""
    for i, ax in enumerate(spec):
        if ax == TP_AXIS:
            k = x.shape[i] // size
            return jax.lax.dynamic_slice_in_dim(
                x, jax.lax.axis_index(TP_AXIS) * k, k, i)
    return x


def _tp_window(body, tp: TPContext, *, n_rest: int, words_index: int,
               n_out: int, donate: bool):
    """Wrap an un-jitted window body in a shard_map over the "model" axis.

    Storage sharded, compute replicated: params and caches arrive as their
    per-shard slices (specs from ``tp``), are all-gathered to the full
    tensors inside the program, and the UNCHANGED window body runs on them —
    so the token stream is bit-exact vs the single-device engine by
    construction (no contraction is ever split, so XLA reduction order never
    enters). The output's cache leaves are sliced back to their shard before
    leaving the program; tokens / words / feeds come out replicated.

    The returned jitted function takes one extra TRAILING argument ``inj`` of
    shape ``(tp, K, S)`` uint32 — per-shard scheduled fault words (the
    fuzzer's shard-targeted surface; zeros when idle; sharded ``P("model")``
    so each shard sees only its own ``(1, K, S)`` slice). Each shard ORs its
    slice into its local ``(K, S)`` word history *before* the cross-shard
    fold::

        words = reduce_or(all_gather(local_words | inj[shard]))

    This is the paper's error-propagation contract applied across the shards
    of one model: a word latched on ANY shard is in EVERY shard's folded
    history, so the host's deferred detection, ``(step, slot)`` attribution
    and LFLR routing behave identically no matter which shard misbehaved —
    no shard can diverge from its peers' recovery decision (the TP analogue
    of "no rank deadlocks waiting for a peer that already failed").
    """
    size = tp.size

    def tp_body(params, caches, *rest_and_inj):
        *rest, inj = rest_and_inj
        pfull = jax.tree_util.tree_map(_tp_gather, params, tp.param_specs)
        cfull = jax.tree_util.tree_map(_tp_gather, caches, tp.cache_specs)
        out = list(body(pfull, cfull, *rest))
        words = out[words_index].astype(jnp.uint32) | inj[0]
        allw = jax.lax.all_gather(words, TP_AXIS)
        out[words_index] = jax.lax.reduce(allw, jnp.uint32(0),
                                          jax.lax.bitwise_or, (0,))
        out[-1] = jax.tree_util.tree_map(
            lambda x, s: _tp_slice(x, s, size), out[-1], tp.cache_specs)
        return tuple(out)

    in_specs = ((tp.param_specs, tp.cache_specs) + (P(),) * n_rest
                + (P(TP_AXIS),))
    out_specs = (P(),) * (n_out - 1) + (tp.cache_specs,)
    mapped = jax.shard_map(tp_body, mesh=tp.mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)
    return jax.jit(mapped, donate_argnums=(1,) if donate else ())


def make_decode_window(cfg: ModelConfig, probe_cfg: ProbeConfig | None = None,
                       *, window: int, donate: bool = True, paged=None,
                       tp: TPContext | None = None):
    """Pipelined decode window: K fused slot-decode steps in one device program.

    The serving hot path must not pay a host-device round trip per token — the
    paper's asynchrony contract (errors latch in-band and raise at the *wait*,
    not eagerly at every operation) applied to decoding. ``lax.scan`` runs
    ``window`` iterations of :func:`make_slot_decode_step` fully on device:
    greedy argmax is computed *inside* the scan and fed back as the next input
    token, so the token chain never touches the host; per-step per-slot error
    words are stacked into a ``(K, slots)`` history so the host can defer fault
    detection to the window boundary and still attribute a fault to its exact
    ``(step, slot)`` (LFLR replays greedy from the last committed boundary —
    deterministic, hence bit-exact).

    Signature of the returned jitted function::

      window_step(params, caches, tokens, pos)
        caches  pytree, leaves (S, ...)   donated when ``donate`` (in-place)
        tokens  (S, 1, 1) int32           input token per slot
        pos     (S,) int32                per-slot absolute position
      → (tokens (K, S) int32,             greedy token emitted per step × slot
         words  (K, S) uint32,            per-(step, slot) error-word history
         [routed (K, S, H) int32,]        MoE only: rows of each step × slot
                                          routed to each of the H held
                                          experts, summed over the layers
         next_tok (S, 1, 1) int32,        device-resident feed for window N+1
         new caches)

    ``next_tok``/``new caches`` let the replica dispatch window N+1 *before*
    reading back window N's token block (double-buffered commit loop): the
    chain's data dependencies live entirely on device.

    With ``paged`` (a :class:`~repro.launch.paging.PagedLayout`) the caches
    argument is the hybrid pool tree and the function takes a trailing
    ``table (S, max_pages) int32`` page-table argument; gather/scatter page
    addressing runs *inside* the window scan, so the zero-sync on-device
    token chain is untouched and the produced tokens are bit-exact vs the
    contiguous layout.

    With ``tp`` (a :class:`TPContext`) the whole window is shard_mapped over
    the "model" mesh axis (:func:`_tp_window`): params/caches are passed as
    their per-shard storage slices, the function takes one extra trailing
    ``inj (tp, K, S) uint32`` per-shard injection argument, and the returned
    word history is the cross-shard OR-fold.
    """
    if window < 1:
        raise ValueError(f"window must be >= 1, got {window}")
    routed = cfg.is_moe
    slot_step = make_slot_decode_step(cfg, probe_cfg, routed=routed)

    if paged is not None:
        pstep = _paged_slot_step(slot_step, paged)

        def paged_window_step(params, hybrid, tokens, pos, table):
            def body(carry, _):
                hybrid, tok, p = carry
                logits, hybrid, words, *pairs = pstep(params, hybrid, tok, p,
                                                      table)
                nxt = jnp.argmax(logits[:, 0, 0, :], axis=-1).astype(jnp.int32)
                return (hybrid, nxt[:, None, None], p + 1), (nxt, words,
                                                             *pairs)

            (hybrid, next_tok, _), (toks, words, *pairs) = jax.lax.scan(
                body, (hybrid, jnp.asarray(tokens, jnp.int32),
                       jnp.asarray(pos, jnp.int32)), None, length=window)
            return (toks, words.astype(jnp.uint32), *pairs, next_tok,
                    hybrid)

        if tp is not None:
            return _tp_window(paged_window_step, tp, n_rest=3,
                              words_index=1, n_out=4 + routed, donate=donate)
        return jax.jit(paged_window_step,
                       donate_argnums=(1,) if donate else ())

    def window_step(params, caches, tokens, pos):
        def body(carry, _):
            caches, tok, p = carry
            logits, caches, words, *pairs = slot_step(params, caches, tok, p)
            nxt = jnp.argmax(logits[:, 0, 0, :], axis=-1).astype(jnp.int32)
            return (caches, nxt[:, None, None], p + 1), (nxt, words, *pairs)

        (caches, next_tok, _), (toks, words, *pairs) = jax.lax.scan(
            body, (caches, jnp.asarray(tokens, jnp.int32),
                   jnp.asarray(pos, jnp.int32)), None, length=window)
        return (toks, words.astype(jnp.uint32), *pairs, next_tok, caches)

    if tp is not None:
        return _tp_window(window_step, tp, n_rest=2, words_index=1,
                          n_out=4 + routed, donate=donate)
    return jax.jit(window_step, donate_argnums=(1,) if donate else ())


def make_prefill_decode_window(cfg: ModelConfig,
                               probe_cfg: ProbeConfig | None = None, *,
                               window: int, donate: bool = True, paged=None,
                               tp: TPContext | None = None):
    """Fused decode+prefill window: chunked prefill rides the decode scan.

    The last synchronous edge of the serving pipeline is admission / LFLR
    re-prefill: a full-length blocking prefill between windows freezes every
    healthy slot while one slot joins or recovers. This window step makes
    prefill a first-class citizen of the decode window (Sarathi-style chunking
    folded into the paper's asynchrony contract): inside the *same*
    ``lax.scan`` dispatch, decoding slots advance by greedy feedback while a
    joining/recovering slot consumes up to K tokens of its prompt chunk —
    per-slot ``jnp.where`` on the input token is the only difference from
    :func:`make_decode_window`, so a window with no chunk is computation-
    identical (bit-exact) to the decode-only window.

    Signature of the returned jitted function::

      window_step(params, caches, tokens, pos, chunk, rem)
        caches  pytree, leaves (S, ...)   donated when ``donate``
        tokens  (S, 1, 1) int32           greedy feedback feed per slot
        pos     (S,) int32                per-slot absolute position
        chunk   (K, S) int32              prompt tokens to feed per step × slot
        rem     (S,) int32                prompt-feed steps for each slot:
                                          step k consumes ``chunk[k, s]`` iff
                                          ``k < rem[s]``, else greedy feedback
      → (tokens (K, S), words (K, S), [routed (K, S, H),] next_tok (S, 1, 1),
         new caches)

    ``routed`` (MoE only) as in :func:`make_decode_window`.

    Flip semantics: when a chunk exhausts a slot's prompt at step ``rem-1``,
    that step's argmax — the logits after the *last* prompt token — is the
    sequence's first generated token, and steps ``rem .. K-1`` continue greedy
    decode for it in the same window. This is exactly the computation the
    synchronous path performs (prefill logits → argmax → feed back), so the
    trajectory is bit-exact vs blocking admission; the host simply knows that
    only steps ``>= rem-1`` of that lane's token block are real. A fault
    latched during a chunk lands in the same ``(K, slots)`` word history as
    decode faults and is attributed to its exact ``(step, slot)`` — recovery
    re-queues the lane without ever blocking the host.

    With ``paged`` the caches argument is the hybrid pool tree and the
    function takes a trailing ``table`` page-table argument (see
    :func:`make_decode_window`); a chunking lane writes its prompt through
    the same gather/scatter addressing, so admission and LFLR page
    re-acquisition ride the window exactly like the contiguous engine.
    """
    if window < 1:
        raise ValueError(f"window must be >= 1, got {window}")
    routed = cfg.is_moe
    slot_step = make_slot_decode_step(cfg, probe_cfg, routed=routed)

    if paged is not None:
        pstep = _paged_slot_step(slot_step, paged)

        def paged_window_step(params, hybrid, tokens, pos, chunk, rem, table):
            rem = jnp.asarray(rem, jnp.int32)

            def body(carry, xs):
                chunk_row, k = xs
                hybrid, tok, p = carry
                feed = (k < rem)[:, None, None]
                inp = jnp.where(feed, chunk_row[:, None, None], tok)
                logits, hybrid, words, *pairs = pstep(params, hybrid, inp, p,
                                                      table)
                nxt = jnp.argmax(logits[:, 0, 0, :], axis=-1).astype(jnp.int32)
                return (hybrid, nxt[:, None, None], p + 1), (nxt, words,
                                                             *pairs)

            (hybrid, next_tok, _), (toks, words, *pairs) = jax.lax.scan(
                body, (hybrid, jnp.asarray(tokens, jnp.int32),
                       jnp.asarray(pos, jnp.int32)),
                (jnp.asarray(chunk, jnp.int32),
                 jnp.arange(window, dtype=jnp.int32)))
            return (toks, words.astype(jnp.uint32), *pairs, next_tok,
                    hybrid)

        if tp is not None:
            return _tp_window(paged_window_step, tp, n_rest=5,
                              words_index=1, n_out=4 + routed, donate=donate)
        return jax.jit(paged_window_step,
                       donate_argnums=(1,) if donate else ())

    def window_step(params, caches, tokens, pos, chunk, rem):
        rem = jnp.asarray(rem, jnp.int32)

        def body(carry, xs):
            chunk_row, k = xs
            caches, tok, p = carry
            feed = (k < rem)[:, None, None]
            inp = jnp.where(feed, chunk_row[:, None, None], tok)
            logits, caches, words, *pairs = slot_step(params, caches, inp, p)
            nxt = jnp.argmax(logits[:, 0, 0, :], axis=-1).astype(jnp.int32)
            return (caches, nxt[:, None, None], p + 1), (nxt, words, *pairs)

        (caches, next_tok, _), (toks, words, *pairs) = jax.lax.scan(
            body, (caches, jnp.asarray(tokens, jnp.int32),
                   jnp.asarray(pos, jnp.int32)),
            (jnp.asarray(chunk, jnp.int32),
             jnp.arange(window, dtype=jnp.int32)))
        return (toks, words.astype(jnp.uint32), *pairs, next_tok, caches)

    if tp is not None:
        return _tp_window(window_step, tp, n_rest=4, words_index=1,
                          n_out=4 + routed, donate=donate)
    return jax.jit(window_step, donate_argnums=(1,) if donate else ())


def make_speculative_decode_window(cfg: ModelConfig,
                                   probe_cfg: ProbeConfig | None = None, *,
                                   window: int, draft_len: int,
                                   draft_layers: int, donate: bool = True,
                                   paged=None, tp: TPContext | None = None):
    """Speculative decode window: draft-and-verify inside one dispatch.

    The zero-sync window (:func:`make_decode_window`) pays one full-model
    forward per emitted token. This window makes the *emission rate* exceed
    the full-model step rate while keeping the paper's asynchrony contract:
    each of the K window steps

    1. **drafts** ``D = draft_len`` tokens per slot with a shallow-exit
       self-draft — the first ``draft_layers`` layers of the *same* weights
       (reusing the same caches, hence the same paged addressing), then the
       final norm + unembedding;
    2. **verifies** all ``D+1`` positions in ONE batched full-model forward
       (:meth:`~repro.models.model.Model.verify_step`): greedy acceptance —
       draft ``d_{i+1}`` survives iff it equals the full model's argmax after
       ``d_i`` — so every emitted token is a full-model argmax and the stream
       is **token-bit-exact** vs the plain window engine, steady and faulted
       (the verify stack reproduces the decode step's arithmetic per row);
    3. records rejected drafts as the in-band, attribution-only
       ``ErrorCode.DRAFT_REJECT`` lane of the ``(K, slots)`` word history —
       a speculation miss is a *local event carried through asynchronous
       execution*, exactly like the paper's soft faults, except the host
       masks it out of the fault-raising word at the wait.

    A rejected draft's cache writes are never rolled back: full-attention
    K/V writes are positional and idempotent, and every stale entry sits at a
    position strictly beyond the accepted prefix, so it is overwritten before
    any masked read reaches it. This is why speculation requires a pure
    full-attention architecture (ring buffers and recurrent states advance
    destructively; :meth:`Model.supports_speculation`).

    Signature of the returned jitted function::

      window_step(params, caches, tokens, pos, chunk, rem[, table])
        caches  pytree, leaves (S, ...)   donated when ``donate``
        tokens  (S, 1, 1) int32           greedy feedback feed per slot
        pos     (S,) int32                per-slot absolute position
                                          (device-resident: advance is
                                          data-dependent, so the position
                                          chain must never touch the host)
        chunk   (K, D+1, S) int32         prompt tokens per step × row × slot
        rem     (S,) int32                total pending prompt tokens per
                                          slot this window (≤ K·(D+1))
      → (tokens (K, S, D+1) int32,        full-model argmaxes per step × slot
         counts (K, S) int32,             consumed positions per step × slot
                                          (prompt rows + accepted tokens,
                                          1 ≤ count ≤ D+1)
         words  (K, S) uint32,            per-(step, slot) error-word history
         next_tok (S, 1, 1) int32,        device-resident feed for window N+1
         next_pos (S,) int32,             device-resident position chain
         new caches)

    Prompt feed rides the verify width: step k of lane s force-feeds its
    next ``rem_k = clip(rem - k·(D+1), 0, D+1)`` pending prompt tokens into
    verify rows ``0 .. rem_k-1`` (forced accepted — they are given, not
    speculated), so admission/LFLR prefill advances up to D+1 tokens per
    full-model step instead of one, and speculation starts *inside* the flip
    step: rows past the prompt chain off the last prompt token's argmax.
    Only rows ``rem_k-1 .. counts[k,s]-1`` of a flip step (and every row
    ``< counts`` of later steps) carry committable tokens; the host commits
    that variable-length stream per lane.

    With ``paged`` the caches argument is the hybrid pool tree plus a
    trailing ``table`` argument; gather/scatter run once per window step
    around the draft+verify pair, and the page probe checks the pages
    covering the *accepted* prefix (a dropped write on an accepted position
    is ledger divergence; rejected positions' dropped writes are not).
    """
    if window < 1:
        raise ValueError(f"window must be >= 1, got {window}")
    if draft_len < 1:
        raise ValueError(f"draft_len must be >= 1, got {draft_len}")
    if not 0 < draft_layers < cfg.num_layers:
        raise ValueError(
            f"draft_layers must be in [1, num_layers), got {draft_layers} "
            f"for {cfg.num_layers} layers")
    model = build_model(cfg)
    if not model.supports_speculation():
        raise ValueError(
            f"{cfg.name}: speculative decode windows require a pure "
            "full-attention, non-MoE architecture (ring buffers and "
            "recurrent states cannot absorb rejected-draft over-writes)")
    D = int(draft_len)
    # probe_cfg is accepted for signature parity with the other window
    # factories; the speculative window probes logits only (the gated
    # architectures have no recurrent state to state-probe), with the same
    # finite-check-only threshold the plain decode step applies to logits.
    probe_threshold = ProbeConfig(loss_divergence_threshold=jnp.inf)

    def _verify_one(params, cache, tokens, pos):
        logits, cache = model.verify_step(params, tokens, cache, pos)
        word = loss_probe(jnp.max(jnp.abs(logits)), probe_threshold)
        return logits, cache, word

    verify_slot = jax.vmap(_verify_one, in_axes=(None, 0, 0, 0))
    draft_chain_slot = jax.vmap(
        lambda params, cache, tok, pos, override, n_forced: model.draft_chain(
            params, tok, cache, pos, draft_layers=draft_layers, draft_len=D,
            override=override, n_forced=n_forced),
        in_axes=(None, 0, 0, 0, 0, 0))
    REJECT = jnp.uint32(int(ErrorCode.DRAFT_REJECT))

    def macro_step(params, views, tok, p, chunk_rows, k, rem):
        """One draft+verify step on (gathered) per-slot cache views.

        ``chunk_rows`` is this step's (D+1, S) prompt-feed block; ``rem`` the
        per-slot total pending prompt tokens for the whole window. Rows still
        inside the prompt are force-fed (and force-accepted); the rest chain
        off the drafter.
        """
        rem_k = jnp.clip(rem - k * (D + 1), 0, D + 1)       # (S,) prompt rows
        # shallow-exit draft chain: D greedy proposals per slot in one call,
        # each row's input overridden by the prompt while the prompt lasts.
        # The drafts' shallow-layer cache writes are recomputed and
        # overwritten by the verify pass below, so they never leak into
        # verified state.
        t0 = jnp.where((rem_k > 0)[:, None, None],
                       chunk_rows[0][:, None, None], tok)
        proposals, views = draft_chain_slot(
            params, views, t0, p, jnp.transpose(chunk_rows[1:]), rem_k)
        seq = jnp.concatenate([t0[:, 0, :], proposals[:, 0, :]],
                              axis=1)                       # (S, D+1)
        # batched full-model verify over all D+1 positions
        vlogits, views, words = verify_slot(params, views, seq[:, None, :], p)
        g = jnp.argmax(vlogits[:, 0, :, :], axis=-1).astype(jnp.int32)
        # acceptance: prompt rows are given (forced), then the leading run of
        # drafts matching the full model's own argmax chain; +1 for the bonus
        # token after the run
        rows = jnp.arange(1, D + 1, dtype=jnp.int32)[None, :]
        ok = (rows < rem_k[:, None]) | (g[:, :D] == seq[:, 1:])
        a = 1 + jnp.cumprod(ok.astype(jnp.int32), axis=1).sum(axis=1)
        a = a.astype(jnp.int32)
        # forced rows (row 0 is always given: prompt or committed feedback);
        # a speculation miss latched iff any *actual* draft was rejected
        forced = jnp.maximum(rem_k, 1)
        words = words | jnp.where((forced <= D) & (a < D + 1), REJECT,
                                  jnp.uint32(0))
        next_tok = jnp.take_along_axis(g, (a - 1)[:, None], axis=1)
        return views, next_tok[:, :, None], p + a, g, a, words

    if paged is not None:

        def paged_window_step(params, hybrid, tokens, pos, chunk, rem, table):
            rem = jnp.asarray(rem, jnp.int32)

            def body(carry, xs):
                chunk_rows, k = xs
                hybrid, tok, p = carry
                views = paged.gather(hybrid, table)
                views, ntok, np_, g, a, words = macro_step(
                    params, views, tok, p, chunk_rows, k, rem)
                hybrid = paged.scatter(hybrid, views, table)
                words = words | paged.probe(table, p + a - 1)
                return (hybrid, ntok, np_), (g, a, words)

            (hybrid, next_tok, next_pos), (toks, counts, words) = jax.lax.scan(
                body, (hybrid, jnp.asarray(tokens, jnp.int32),
                       jnp.asarray(pos, jnp.int32)),
                (jnp.asarray(chunk, jnp.int32),
                 jnp.arange(window, dtype=jnp.int32)))
            return (toks, counts.astype(jnp.int32), words.astype(jnp.uint32),
                    next_tok, next_pos, hybrid)

        if tp is not None:
            return _tp_window(paged_window_step, tp, n_rest=5,
                              words_index=2, n_out=6, donate=donate)
        return jax.jit(paged_window_step,
                       donate_argnums=(1,) if donate else ())

    def window_step(params, caches, tokens, pos, chunk, rem):
        rem = jnp.asarray(rem, jnp.int32)

        def body(carry, xs):
            chunk_rows, k = xs
            caches, tok, p = carry
            caches, ntok, np_, g, a, words = macro_step(
                params, caches, tok, p, chunk_rows, k, rem)
            return (caches, ntok, np_), (g, a, words)

        (caches, next_tok, next_pos), (toks, counts, words) = jax.lax.scan(
            body, (caches, jnp.asarray(tokens, jnp.int32),
                   jnp.asarray(pos, jnp.int32)),
            (jnp.asarray(chunk, jnp.int32),
             jnp.arange(window, dtype=jnp.int32)))
        return (toks, counts.astype(jnp.int32), words.astype(jnp.uint32),
                next_tok, next_pos, caches)

    if tp is not None:
        return _tp_window(window_step, tp, n_rest=4, words_index=2, n_out=6,
                          donate=donate)
    return jax.jit(window_step, donate_argnums=(1,) if donate else ())


def make_chunked_prefill(cfg: ModelConfig,
                         probe_cfg: ProbeConfig | None = None, *,
                         chunk: int, donate: bool = False, paged=None):
    """Standalone chunked prefill: advance an *existing* cache by ≤C tokens.

    ``chunk_step(params, cache, tokens, n, start_pos)`` for ``tokens`` of
    static shape (B, C) feeds ``tokens[:, :n]`` (traced ``n``) through the
    decode step starting at ``start_pos`` → ``(last logits, cache, word)``.
    One compile serves every chunk length ≤ C.

    This is the building block the fused window embeds: chaining chunks is
    bit-identical to :func:`make_cache_prefill` over the concatenation
    (same decode step, same positions), so a prefill split across decode
    windows reproduces the one-shot trajectory exactly. Unlike
    ``make_cache_prefill`` it takes the cache as an argument — the caller owns
    allocation, which is what lets a serving lane resume a half-built cache
    chunk by chunk.

    With ``paged`` the signature becomes ``chunk_step(params, hybrid, row,
    slot, tokens, n, start_pos)``: the advanced cache lives in the shared
    pool, addressed through one slot's ``(max_pages,)`` page-table ``row``
    (writes to unmapped pages drop; the page probe latches ``PAGE_FAULT``),
    and dense (non-paged) state is read/written at ``slot`` of the stacked
    tree. Chaining paged chunks is bit-identical to the contiguous chain for
    the same reason the contiguous chain matches the one-shot prefill: same
    decode step, same positions, and the gathered view is bit-equal to the
    contiguous cache.
    """
    if chunk < 1:
        raise ValueError(f"chunk must be >= 1, got {chunk}")
    step_fn = make_decode_step(cfg, probe_cfg)

    if paged is not None:

        def paged_chunk_step(params, hybrid, row, slot, tokens, n, start_pos):
            tokens = jnp.asarray(tokens, jnp.int32)
            logits0 = jnp.zeros((tokens.shape[0], 1, cfg.vocab_size),
                                jnp.float32)

            def body(i, carry):
                hybrid, word, _ = carry
                view = paged.gather_slot(hybrid, row, slot)
                tok = jax.lax.dynamic_slice_in_dim(tokens, i, 1, axis=1)
                p = jnp.asarray(start_pos, jnp.int32) + i
                logits, view, w = step_fn(params, view, tok, p)
                hybrid = paged.scatter_slot(hybrid, view, row, slot)
                w = w | paged.probe(row[None, :], p[None])[0]
                return (hybrid, word | w, logits.astype(jnp.float32))

            hybrid, word, logits = jax.lax.fori_loop(
                0, jnp.asarray(n, jnp.int32), body,
                (hybrid, jnp.uint32(0), logits0))
            return logits, hybrid, word

        return jax.jit(paged_chunk_step,
                       donate_argnums=(1,) if donate else ())

    def chunk_step(params, cache, tokens, n, start_pos):
        tokens = jnp.asarray(tokens, jnp.int32)
        logits0 = jnp.zeros((tokens.shape[0], 1, cfg.vocab_size), jnp.float32)

        def body(i, carry):
            cache, word, _ = carry
            tok = jax.lax.dynamic_slice_in_dim(tokens, i, 1, axis=1)
            logits, cache, w = step_fn(params, cache, tok,
                                       jnp.asarray(start_pos, jnp.int32) + i)
            return (cache, word | w, logits.astype(jnp.float32))

        cache, word, logits = jax.lax.fori_loop(
            0, jnp.asarray(n, jnp.int32), body,
            (cache, jnp.uint32(0), logits0))
        return logits, cache, word

    return jax.jit(chunk_step, donate_argnums=(1,) if donate else ())


def make_cache_prefill(cfg: ModelConfig, probe_cfg: ProbeConfig | None = None,
                       *, fused: bool = False, paged=None,
                       donate: bool = False):
    """Cache-producing prefill built by reusing the decode step.

    Returns ``prefill(params, tokens, max_len, start_pos=0)`` for ``tokens``
    of shape (B, S) → ``(last-position logits, cache, combined error word)``.

    This is the recompute path of serving LFLR: re-running it over
    prompt + generated tokens rebuilds a poisoned sequence's state exactly
    (greedy decode is deterministic), so recovery never restarts the request.

    Two implementations, both token-by-token through the *same* decode step
    (sharing the step is what makes the LFLR recompute reproduce the batched
    trajectory exactly):

    * ``fused=False`` — a host loop of S jitted step dispatches (the PR-1
      path: simple, one compile, but S dispatch overheads per prefill);
    * ``fused=True``  — one jitted ``lax.fori_loop`` whose trip count is the
      *traced* real length: tokens are padded to the (static) cache capacity
      so one compile serves every prompt/recompute length, but only the real
      steps execute — no wasted padded iterations, no masking, and the body
      is the same decode step, so the result is bit-identical to the loop.
      This is the serving window engine's admission/LFLR path: one dispatch
      per prefill instead of S.

    With ``paged`` the signature becomes ``prefill(params, hybrid, row, slot,
    tokens, start_pos=0)`` (``fused`` implied): the rebuilt cache is written
    straight into the slot's pool pages through its page-table ``row``, after
    an in-program scrub of those pages and a fresh reset of the slot's dense
    state — the whole blocking re-prefill is one dispatch and never leaves
    stale (possibly poisoned) bytes behind in a recycled page.
    """
    model = build_model(cfg)
    step_fn = make_decode_step(cfg, probe_cfg)

    if paged is not None:
        # donate: the hybrid argument is the FULL multi-slot pool — an
        # out-of-place update here would transiently double the very HBM the
        # paged layout exists to save (the caller must rebind its pool to the
        # returned tree before any retry)
        chunked = make_chunked_prefill(cfg, probe_cfg, chunk=paged.max_len,
                                       paged=paged, donate=donate)

        @functools.partial(jax.jit, donate_argnums=(0,) if donate else ())
        def fresh_slot(hybrid, row, slot):
            hybrid = paged.scrub(hybrid, row)
            return paged.reset_slot(hybrid, model.init_cache(1, paged.max_len),
                                    slot)

        def prefill(params, hybrid, row, slot, tokens, start_pos: int = 0):
            tokens = jnp.asarray(tokens, jnp.int32)
            if tokens.ndim != 2 or tokens.shape[1] == 0:
                raise ValueError(f"tokens must be (B, S>0), got {tokens.shape}")
            _, S = tokens.shape
            if S > paged.max_len:
                raise ValueError(
                    f"prompt of {S} tokens exceeds capacity {paged.max_len}")
            hybrid = fresh_slot(hybrid, jnp.asarray(row, jnp.int32),
                                jnp.int32(slot))
            padded = jnp.pad(tokens, ((0, 0), (0, paged.max_len - S)))
            logits, hybrid, word = chunked(
                params, hybrid, jnp.asarray(row, jnp.int32), jnp.int32(slot),
                padded, jnp.int32(S), jnp.int32(start_pos))
            return logits, hybrid, word

        return prefill

    if not fused:
        step = jax.jit(step_fn)

        def prefill(params, tokens, max_len: int, start_pos: int = 0):
            tokens = jnp.asarray(tokens, jnp.int32)
            if tokens.ndim != 2 or tokens.shape[1] == 0:
                raise ValueError(f"tokens must be (B, S>0), got {tokens.shape}")
            _, S = tokens.shape
            cache = model.init_cache(tokens.shape[0], max_len)
            word = jnp.uint32(0)
            logits = None
            for i in range(S):
                logits, cache, w = step(params, cache, tokens[:, i:i + 1],
                                        jnp.int32(start_pos + i))
                word = word | w
            return logits, cache, word

        return prefill

    @functools.partial(jax.jit, static_argnums=(2,))
    def run(params, tokens_padded, max_len: int, n, start_pos):
        B, _ = tokens_padded.shape
        cache0 = model.init_cache(B, max_len)
        logits0 = jnp.zeros((B, 1, cfg.vocab_size), jnp.float32)

        def body(i, carry):
            cache, word, _ = carry
            tok = jax.lax.dynamic_slice_in_dim(tokens_padded, i, 1, axis=1)
            logits, cache, w = step_fn(params, cache, tok, start_pos + i)
            return (cache, word | w, logits.astype(jnp.float32))

        return jax.lax.fori_loop(0, n, body,
                                 (cache0, jnp.uint32(0), logits0))

    def prefill(params, tokens, max_len: int, start_pos: int = 0):
        tokens = jnp.asarray(tokens, jnp.int32)
        if tokens.ndim != 2 or tokens.shape[1] == 0:
            raise ValueError(f"tokens must be (B, S>0), got {tokens.shape}")
        _, S = tokens.shape
        if S > max_len:
            raise ValueError(f"prompt of {S} tokens exceeds capacity {max_len}")
        padded = jnp.pad(tokens, ((0, 0), (0, max_len - S)))
        cache, word, last = run(params, padded, int(max_len), jnp.int32(S),
                                jnp.int32(start_pos))
        return last, cache, word

    return prefill


def _recurrent_states(cache) -> list:
    out = []

    def visit(path, leaf):
        keys = [getattr(k, "key", None) for k in path]
        if any(k in ("ssm", "h") for k in keys):
            out.append(leaf)
        return leaf

    jax.tree_util.tree_map_with_path(visit, cache)
    return out


def make_reset_opt_fn(cfg: ModelConfig):
    """Paper use case 2: optimizer-moment reset + lr decay ('solver restart')."""

    @jax.jit
    def reset(state, lr_scale):
        return {"params": state["params"],
                "opt": reset_moments(state["opt"]),
                "step": state["step"],
                "lr_scale": state["lr_scale"] * lr_scale}

    return reset


# ------------------------------------------------------------------ input specs
def _tok(shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32)


def batch_specs(cfg: ModelConfig, shape: ShapeConfig) -> dict:
    """ShapeDtypeStruct stand-ins for one global batch (train / prefill)."""
    B, S = shape.global_batch, shape.seq_len
    batch: dict[str, Any] = {"labels": _tok((B, S))}
    if cfg.family == "audio":
        batch["inputs_embeds"] = jax.ShapeDtypeStruct((B, S, cfg.d_model),
                                                      jnp.bfloat16)
    else:
        batch["tokens"] = _tok((B, S))
    if cfg.family == "vlm":
        batch["img_embeds"] = jax.ShapeDtypeStruct(
            (B, cfg.img_tokens, cfg.d_model), jnp.bfloat16)
    return batch


def state_specs(cfg: ModelConfig) -> dict:
    model = build_model(cfg)
    params = model.param_shapes()
    opt = jax.eval_shape(init_opt_state, params)
    return {"params": params, "opt": opt,
            "step": jax.ShapeDtypeStruct((), jnp.int32),
            "lr_scale": jax.ShapeDtypeStruct((), jnp.float32)}


def state_shardings(cfg: ModelConfig, mesh) -> dict:
    specs = state_specs(cfg)
    return {
        "params": param_shardings(specs["params"], mesh),
        "opt": {k: moment_shardings(specs["params"], mesh)
                for k in ("m", "v")},
        "step": NamedSharding(mesh, P()),
        "lr_scale": NamedSharding(mesh, P()),
    }


def input_specs(cfg: ModelConfig, shape: ShapeConfig, mesh, *,
                kind: str | None = None, perf: PerfOptions = BASELINE):
    """(args, in_shardings) for the cell's step function.

    train  → (state, batch, inject)
    prefill→ (params, batch)
    decode → (params, cache, token, pos)
    """
    kind = kind or shape.kind
    repl = NamedSharding(mesh, P())
    if kind == "train":
        st = state_specs(cfg)
        batch = batch_specs(cfg, shape)
        args = (st, batch, jax.ShapeDtypeStruct((), jnp.uint32))
        shardings = (state_shardings(cfg, mesh), batch_shardings(batch, mesh),
                     repl)
        return args, shardings
    if kind == "prefill":
        st = state_specs(cfg)["params"]
        batch = batch_specs(cfg, shape)
        return (st, batch), (param_shardings(st, mesh),
                             batch_shardings(batch, mesh))
    if kind == "decode":
        model = build_model(cfg)
        st = state_specs(cfg)["params"]
        B = shape.global_batch
        cache = model.cache_shapes(B, shape.seq_len)
        token = _tok((B, 1))
        shard_seq = shape.name == "long_500k"
        args = (st, cache, token, jax.ShapeDtypeStruct((), jnp.int32))
        shardings = (param_shardings(st, mesh),
                     cache_shardings(cache, mesh, shard_seq=shard_seq,
                                     seq_over_model=perf.cache_seq_model),
                     batch_shardings({"t": token}, mesh)["t"], repl)
        return args, shardings
    raise ValueError(kind)


def make_step_for(cfg: ModelConfig, shape: ShapeConfig, *, impl: str = "auto",
                  perf: PerfOptions = BASELINE):
    if shape.kind == "train":
        return make_train_step(cfg, impl=impl, perf=perf)
    if shape.kind == "prefill":
        return make_prefill_step(cfg, impl=impl)
    return make_decode_step(cfg)
