"""Sparse experts on the serving path, on a smoke Qwen3-MoE (8 experts,
top-2, two layers, float32) split over 2 and 4 expert shards.

- the shares' outputs of one layer add up to the unsharded layer's, every
  routed pair is counted by exactly one share, and the training path's
  share agrees where nothing is dropped;
- prefill and then cached decode agree on logits with the plain reference
  (``bench/reference/moe_transformer.py``), and what ``Replica`` serves is
  the reference's greedy choice;
- a request's served tokens are the same alone and beside full slots;
- an LFLR-recovered slot replays bit-exactly;
- each window's ``serve.commit`` span counts the pairs routed here.
"""
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, smoke_config
from repro.launch.steps import make_cache_prefill
from repro.models import build_model
from repro.models.moe import apply_moe, apply_moe_dropless, init_moe
from repro.serve import EngineConfig, Replica, Request

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MAX_LEN = 64


def _reference():
    path = os.path.join(ROOT, "bench", "reference", "moe_transformer.py")
    spec = importlib.util.spec_from_file_location("moe_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _cfg(shards, shard=0):
    return smoke_config("qwen3-moe-30b-a3b").replace(expert_shards=shards,
                                                     expert_shard=shard)


def _ref_cfg(cfg) -> dict:
    """The reference's reading of a program config (bench config keys)."""
    return {"num_attention_heads": cfg.num_heads,
            "num_key_value_heads": cfg.num_kv_heads,
            "head_dim": cfg.resolved_head_dim, "norm": cfg.norm,
            "norm_eps": 1e-6, "rope_theta": cfg.rope_theta,
            "num_experts_per_tok": cfg.num_experts_per_tok,
            "num_experts_held": cfg.experts_held,
            "expert_shard": cfg.expert_shard,
            "tie_word_embeddings": cfg.tie_embeddings}


@pytest.fixture(scope="module")
def env():
    cfg = _cfg(2, 1)
    return cfg, build_model(cfg).init(jax.random.PRNGKey(0))


def test_ep16_entry_is_one_share_of_the_published_model():
    whole, share = get_config("qwen3-moe-30b-a3b"), get_config(
        "qwen3-moe-30b-a3b-ep16")
    assert (share.num_experts, share.num_experts_per_tok, share.d_ff) == (
        whole.num_experts, whole.num_experts_per_tok, whole.d_ff)
    assert (share.expert_shards, share.expert_shard, share.experts_held) == (
        16, 0, 8)
    assert whole.experts_held == whole.num_experts == 128
    layer = jax.eval_shape(lambda: init_moe(jax.random.PRNGKey(0), share))
    assert layer["router"].shape == (2048, 128)
    assert layer["wi"].shape == layer["wg"].shape == (8, 2048, 768)
    assert layer["wo"].shape == (8, 768, 2048)


@pytest.mark.parametrize("shards", [2, 4])
def test_shares_add_up_to_the_whole_layer(shards):
    whole = _cfg(1)
    key = jax.random.PRNGKey(3)
    p = init_moe(key, whole)
    x = jax.random.normal(jax.random.fold_in(key, 1), (5, 3, whole.d_model))
    want, pairs = apply_moe_dropless(p, x, whole)
    got = jnp.zeros_like(want)
    counted = []
    for i in range(shards):
        cfg = _cfg(shards, i)
        H = cfg.experts_held
        mine = {k: (v if k == "router" else v[i * H:(i + 1) * H])
                for k, v in p.items()}
        part, n = apply_moe_dropless(mine, x, cfg)
        got = got + part
        counted.append(n)
        # the training path's share, with capacity to spare, is the same
        cap, aux = apply_moe(mine, x, cfg.replace(expert_capacity_factor=8.0))
        assert float(aux["dropped_fraction"]) == 0.0
        np.testing.assert_allclose(np.asarray(cap), np.asarray(part),
                                   rtol=1e-5, atol=1e-6)
    # float32 on the CPU: the parts differ from the whole only by the order
    # in which the chosen experts' terms are summed
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.concatenate(counted), np.asarray(pairs))
    assert int(pairs.sum()) == 5 * 3 * whole.num_experts_per_tok


def test_cached_decode_matches_reference_logits(env):
    """Prefill a prompt through the cache, then decode greedily through it:
    every next-token logit vector against the reference's full forward pass
    over the same tokens. Both are float32; the reference multiplies at
    ``highest`` precision and sums in another order, so they agree to
    rounding (1e-4 of logits of order one), far below the gaps between a
    position's logits."""
    cfg, params = env
    model = build_model(cfg)
    prompt = [7, 3, 250, 11, 42, 9]
    logits, cache, word = make_cache_prefill(cfg, fused=True)(
        params, jnp.asarray([prompt], jnp.int32), MAX_LEN)
    assert int(word) == 0
    got, toks = [np.asarray(logits[0, -1])], list(prompt)
    step = jax.jit(model.decode_step)
    for pos in range(len(prompt), len(prompt) + 10):
        toks.append(int(np.argmax(got[-1])))
        lg, cache = step(params, jnp.asarray([[toks[-1]]], jnp.int32), cache,
                         jnp.int32(pos))
        got.append(np.asarray(lg[0, -1]))
    rows = np.asarray([(0, s) for s in range(len(prompt) - 1, len(toks))])
    ref = np.concatenate([lg for _, lg in _reference().position_logits(
        params, _ref_cfg(cfg), np.asarray([toks], np.int32), rows)])
    np.testing.assert_allclose(np.stack(got), ref, rtol=1e-4, atol=1e-4)


def _replica(env, num_slots=4):
    cfg, params = env
    return Replica(cfg, params, config=EngineConfig(
        num_slots=num_slots, max_len=MAX_LEN, window=4, overlap=True))


def _serve(rep, reqs, inject_at=None, rid=0):
    for r in reqs:
        assert rep.submit(r) is None
    out, steps = {}, 0
    while not rep.idle():
        if inject_at is not None and steps == inject_at:
            slot = next(s.idx for s in rep.sched.slots
                        if s.active and s.req.id == rid)
            assert rep.inject_state_fault(slot) == slot
        for resp in rep.step():
            out[resp.id] = resp
        steps += 1
        assert steps < 500
    return out


def _requests(n):
    return [Request(id=i, prompt=tuple(5 + 17 * i + j for j in range(3 + i)),
                    max_new_tokens=10 + i) for i in range(n)]


def test_replica_serves_the_reference_greedy_tokens(env):
    """What the window engine serves, checked position by position: the
    served token's reference logit lies within rounding (1e-4, as above) of
    the reference's best."""
    cfg, params = env
    reqs = _requests(4)
    out = _serve(_replica(env), reqs)
    ref = _reference()
    for r in reqs:
        toks = list(r.prompt) + list(out[r.id].tokens)
        assert out[r.id].ok and len(out[r.id].tokens) == r.max_new_tokens
        rows = np.asarray([(0, len(r.prompt) - 1 + i)
                           for i in range(r.max_new_tokens)])
        lg = np.concatenate([x for _, x in ref.position_logits(
            params, _ref_cfg(cfg), np.asarray([toks], np.int32), rows)])
        served = np.asarray(out[r.id].tokens)
        gap = lg.max(axis=1) - lg[np.arange(len(served)), served]
        assert gap.max() <= 1e-4, (r.id, gap)


def test_served_tokens_do_not_depend_on_neighbours(env):
    """Dropless: one request alone, and the same request with every other
    slot busy, gets the same tokens."""
    alone = _serve(_replica(env), _requests(1))
    crowded = _serve(_replica(env), _requests(4))
    assert alone[0].tokens == crowded[0].tokens


def test_lflr_recovered_slot_replays_bit_exactly(env):
    reqs = _requests(3)
    clean = _serve(_replica(env), reqs)
    rep = _replica(env)
    hit = _serve(rep, reqs, inject_at=3, rid=1)
    assert rep.metrics.faults, "no fault was detected"
    assert hit[1].retries >= 1
    for r in reqs:
        assert hit[r.id].ok and hit[r.id].tokens == clean[r.id].tokens


def test_commit_spans_count_the_routed_pairs():
    """With every expert held, each lane that ran a request routes
    ``num_experts_per_tok`` rows a layer a step to this model: the window's
    ``moe_pairs`` is exactly lanes x K x layers x top-k."""
    from repro.obs.trace import Tracer
    cfg = _cfg(1)
    tracer = Tracer()
    rep = Replica(cfg, build_model(cfg).init(jax.random.PRNGKey(0)),
                  config=EngineConfig(num_slots=4, max_len=MAX_LEN, window=4,
                                      overlap=True), tracer=tracer)
    _serve(rep, _requests(3))
    ev = [e for e in tracer.events() if e.get("ph") == "X"]
    lanes = {e["args"]["window"]: e["args"]["lanes"] for e in ev
             if e["name"] == "serve.dispatch"}
    commits = [e["args"] for e in ev if e["name"] == "serve.commit"]
    assert commits and all("moe_pairs" in a for a in commits)
    per_lane = 4 * cfg.num_layers * cfg.num_experts_per_tok
    for a in commits:
        assert a["moe_pairs"] == lanes[a["window"]] * per_lane
        assert a["moe_pairs"] / cfg.experts_held <= a["moe_pairs_max"]
        assert a["moe_pairs_max"] <= a["moe_pairs"]
