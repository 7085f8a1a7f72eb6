"""The comparison that decides ``correct``, at a size a CPU test can hold.

A whole run of the harness (``run.run_cell``, without its look for a chip)
on qwen3-1.7b cut to four layers, with qwen3's limit:

- a sound run reads ``correct``;
- the timed path broken underneath reads not ``correct``, once for each
  fault a serving cell can have: a window that returns its cache unchanged,
  half of the slots left out (the other half's tokens served in their
  place), and one token altered where the window produces it;
- the float8 control, put in the program's place in the comparison, reads
  not ``correct``, while the served tokens of the same run stay within the
  limit.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q bench
"""
from __future__ import annotations

import copy
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(os.path.dirname(HERE), "src"), HERE]

import run  # noqa: E402


def tiny_spec(faults: bool = False) -> dict:
    with open(os.path.join(HERE, "configs", "qwen3-1.7b.json")) as f:
        conf = json.load(f)
    # qwen3's widths and vocabulary, four layers: the control's gap grows
    # with the vocabulary, the width and the depth, so a narrow or a
    # shallower model would not read it
    conf.update(num_hidden_layers=4,
                engine={"num_slots": 4, "max_len": 128, "window": 4,
                        "overlap": True})
    mix = {"loop": "open", "rate_per_s": 3.0, "warm_s": 1.0,
           "prompt": {"median": 16, "sigma": 0.6, "min": 6, "max": 40},
           "output": {"median": 10, "sigma": 0.5, "min": 5, "max": 24},
           "check_tokens": 120, "drain_s": 60}
    if faults:
        mix["faults"] = {"kind": "state", "rate_per_s": 1.0}
        # a fault a second over 4 slots can hit one request more often
        # than the engine's default of 2 retries allows
        conf["engine"]["max_request_retries"] = 6
    return {"cell": {"name": "tiny", "chips": 1}, "config": conf, "mix": mix,
            "end_to_end": [], "per_layer": []}


def _run(spec, plant=None, control=False, seed=2**31 + 11):
    return run.run_cell(spec, seed=seed, seconds=3.0, trace=False, chips=1,
                        platform="cpu", plant=plant, control=control)


def _wrap(replica, edit):
    """Swap the replica's window program for one built without donation (so
    the planted fault may hand back its input) and edit its outputs."""
    from repro.launch.steps import make_prefill_decode_window
    from repro.serve.replica import SERVE_PROBES

    fn = make_prefill_decode_window(replica.cfg, SERVE_PROBES,
                                    window=replica.window, donate=False)

    def window(params, caches, *rest):
        return edit(caches, *fn(params, caches, *rest))

    replica._decode_window = window


def stale_state(replica):
    _wrap(replica, lambda caches, toks, words, nxt, new: (toks, words, nxt,
                                                          caches))


def half_batch(replica):
    def edit(caches, toks, words, nxt, new):
        h = toks.shape[1] // 2
        toks = toks.at[:, h:].set(toks[:, :toks.shape[1] - h])
        nxt = nxt.at[h:].set(nxt[:nxt.shape[0] - h])
        return toks, words, nxt, new
    _wrap(replica, edit)


def token_altered(replica):
    V = replica.cfg.vocab_size

    def edit(caches, toks, words, nxt, new):
        k = toks.shape[0] // 2
        return toks.at[k].set((toks[k] + 1) % V), words, nxt, new
    _wrap(replica, edit)


@pytest.fixture(scope="module")
def spec():
    s = tiny_spec()
    if s["config"]["limits"]["gap_sigma"] is None:
        pytest.fail("qwen3-1.7b.json has no gap_sigma limit")
    return s


def test_sound_run_is_correct(spec):
    out = _run(spec)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0


def test_sound_run_with_faults_is_correct(spec):
    s = tiny_spec(faults=True)
    out = _run(s)
    assert out["correct"], out["checks"]
    assert out["checks"]["recovered_in_sample"]["value"] >= 1


@pytest.mark.parametrize("plant", [stale_state, half_batch, token_altered],
                         ids=lambda f: f.__name__)
def test_broken_timed_path_is_not_correct(spec, plant):
    out = _run(copy.deepcopy(spec), plant=plant)
    assert not out["correct"], out["checks"]


def test_fp8_control_fails_the_limit(spec):
    out = _run(spec, control=True)
    limit = spec["config"]["limits"]["gap_sigma"]
    assert not out["correct"], out["checks"]
    assert out["checks"]["gap_sigma"]["value"] > limit
    assert out["program_gap"] <= limit
