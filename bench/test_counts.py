"""bench/counts.py against bytes and operations worked by hand for one
qwen3-1.7b decode step at 16 slots, lane i at position 100·i.

Per layer: wq 2048·16·128 + wk, wv 2·2048·8·128 + wo 16·128·2048
= 12,582,912 and the SwiGLU MLP 3·2048·6144 = 37,748,736 weights, so
50,331,648 (100,663,296 B in bf16), plus two f32 norm scales (16,384 B) and
the bf16 q/k-norm scales (512 B). 28 layers: 2,819,045,376 B. The final norm
(8,192 B) and the tied 151936·2048 table, read whole by the unembedding
(622,329,856 B), make 3,441,383,424 B of weights per step.

KV: 2 (K and V) · 8 heads · 128 · 2 B · 28 layers = 114,688 B a position.
A lane at position p reads its p earlier positions and writes one:
Σ(p_i + 1) = 100·120 + 16 = 12,016 positions, 1,378,091,008 B.

Operations: 2 per weight (28 · 50,331,648 + the 311,164,928-weight
unembedding) = 3,440,902,144 per token, 55,054,434,304 for 16; attention
4 · 28 layers · 16 heads · 128 · 12,016 = 2,756,182,016.
"""
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import counts  # noqa: E402


def _conf(name):
    with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
        return json.load(f)


def test_qwen3_decode_step_at_16_slots():
    c = _conf("qwen3-1.7b")
    assert counts.weight_bytes_per_step(c) == 3_441_383_424
    assert counts.kv_bytes_per_position(c) == 114_688
    flops, nbytes = counts.step_cost(c, [100 * i for i in range(16)])
    assert nbytes == 3_441_383_424 + 1_378_091_008
    assert flops == 55_054_434_304 + 2_756_182_016


def test_kv_term_is_bounded_by_position_not_capacity():
    c = _conf("qwen3-1.7b")
    _, fresh = counts.step_cost(c, [0] * 16)
    assert fresh == 3_441_383_424 + 16 * 114_688


def test_window_is_k_steps_advancing_one_position_each():
    c = _conf("qwen3-1.7b")
    f, b = counts.window_cost(c, 8, [0, 40])
    steps = [counts.step_cost(c, [k, 40 + k]) for k in range(8)]
    assert (f, b) == (sum(s[0] for s in steps), sum(s[1] for s in steps))


def test_sliding_window_caps_the_attended_positions():
    c = _conf("starcoder2-3b")
    assert counts.attended(c, 100) == 100
    assert counts.attended(c, 10_000) == 4095
    # tied: the table is read whole by the unembedding
    w = counts.weight_bytes_per_step(c)
    _, b = counts.step_cost(c, [0])
    assert b == w + counts.kv_bytes_per_position(c)
    # untied: the unembedding matrix is read, the embedding only by rows
    u = dict(c, tie_word_embeddings=False)
    _, b = counts.step_cost(u, [0])
    assert b == (counts.weight_bytes_per_step(u)
                 + counts.kv_bytes_per_position(u) + 3072 * 2)
