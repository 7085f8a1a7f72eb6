"""bench/counts_moe.py against a count worked by hand, and the MoE
configuration file against the registry entry it names.

A small shape: d 64, 4 query and 2 KV heads of 16, 2 layers, 8 experts of
width 32 with 2 held, vocabulary 100, untied, bf16.

Per layer: attention 64·4·16 + 2·64·2·16 + 4·16·64 = 12,288 weights
(24,576 B), two f32 norm scales (512 B), the bf16 q/k-norm scales (64 B);
the held experts 2 · 3·64·32 = 12,288 weights (24,576 B) and the f32 router
64·8 (2,048 B): 51,776 B. Two layers 103,552 B, the final norm 256 B and the
100·64 unembedding 12,800 B: 116,608 B of weights a step.

KV: 2 · 2 heads · 16 · 2 B · 2 layers = 256 B a position. Lanes at 3 and 10
read and write 4 + 11 positions (3,840 B) and look up two embedding rows
(256 B): 120,704 B.

Operations: 2 per weight (2 · 12,288 + 6,400) = 61,952 a token; attention
4 · 2 layers · 4 heads · 16 · 15 = 7,680; the router 2 · 2 · 64 · 8 = 2,048
a token; 5 routed pairs 2 · 3·64·32 · 5 = 61,440: 197,120 in all.
"""
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(os.path.dirname(HERE), "src"), HERE]

import counts_moe  # noqa: E402

SMALL = {"hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
         "head_dim": 16, "num_hidden_layers": 2, "intermediate_size": 32,
         "moe_intermediate_size": 32, "num_experts": 8, "num_experts_held": 2,
         "vocab_size": 100, "tie_word_embeddings": False, "hidden_act": "silu",
         "norm": "rmsnorm", "qk_norm": True, "torch_dtype": "bfloat16",
         "sliding_window": None}


def test_small_step_by_hand():
    assert counts_moe.weight_bytes_per_step(SMALL) == 116_608
    flops, nbytes = counts_moe.step_cost(SMALL, [3, 10], pairs=5)
    assert nbytes == 120_704
    assert flops == 197_120


def test_window_counts_pairs_once_and_steps_k_times():
    f, b = counts_moe.window_cost(SMALL, 4, [0, 7], pairs=9)
    steps = [counts_moe.step_cost(SMALL, [k, 7 + k], 0) for k in range(4)]
    assert b == sum(s[1] for s in steps)
    assert f == sum(s[0] for s in steps) + 2 * 3 * 64 * 32 * 9


def test_configuration_file_matches_its_registry_entry():
    import run
    with open(os.path.join(HERE, "configs", "qwen3-moe-30b-a3b.json")) as f:
        conf = json.load(f)
    cfg = run.model_config(conf)
    assert cfg.name == conf["registry_name"] == "qwen3-moe-30b-a3b-ep16"
    assert cfg.num_experts == conf["num_experts"] == 128
    assert cfg.experts_held == conf["num_experts_held"] == 8
    assert cfg.expert_shards == conf["expert_shards"] == 16
    assert cfg.expert_shard == conf["expert_shard"] == 0
    assert cfg.num_experts_per_tok == conf["num_experts_per_tok"] == 8
    assert cfg.d_ff == conf["moe_intermediate_size"] == 768
    assert cfg.vocab_size == conf["vocab_size"] == 151936
    assert not cfg.tie_embeddings and cfg.dtype == "bfloat16"
