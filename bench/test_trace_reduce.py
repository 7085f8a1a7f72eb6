"""bench/trace_reduce.py on hand-made intervals and on a small trace
recorded on a TPU v5e (``testdata/small.xplane.pb``): three runs of a
``window_step`` program (a scan of four 1024² bf16 matmuls, ~58 µs each)
and of an ``enum`` reduction (~3.8 µs), with the host annotating each
``bench.step`` and then sleeping 5 ms inside ``bench.idle``."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import trace_reduce as tr  # noqa: E402


def test_union_merges_and_clips():
    iv = [(5, 8, "a"), (0, 2, "b"), (1, 3, "c"), (7, 12, "d")]
    assert tr.union(iv, 1, 10) == [(1, 3), (5, 10)]
    assert tr.gaps_of([(1, 3), (5, 10)], 0, 11) == [(0, 1), (3, 5),
                                                    (10, 11)]


def test_self_time_excludes_nested_operations():
    ev = [(0, 10, "while"), (1, 4, "dot"), (5, 9, "dot"), (11, 12, "copy")]
    out = tr.self_times(ev, 0, 100)
    assert out == {"while": 3.0, "dot": 7.0, "copy": 1.0}


def test_program_cut_by_the_window_counts_for_its_share():
    tr_ = {"ops": {"/device:TPU:0": [(0, 10, "a"), (10, 30, "b")]},
           "modules": {"/device:TPU:0": [(0, 10, "jit_w"), (10, 30, "jit_w")]},
           "host": [(5, 5, "bench.mark"), (20, 20, "bench.mark")]}
    p = tr.reduce(tr_)["programs"]["jit_w"]
    assert p["n"] == pytest.approx(1.0) and p["s"] == pytest.approx(15e-9)
    assert p["s"] / p["n"] == pytest.approx(15e-9)


def test_gap_takes_the_host_span_that_overlaps_it_most():
    host = [(0, 10, "replica.step"), (9, 30, "bench.wait"),
            (12, 14, "bench.offer")]
    assert tr.label((10, 20), host) == "bench.wait"
    assert tr.label((12, 14), host) == "bench.offer"    # innermost on a tie
    assert tr.label((40, 50), host) == "unlabelled"


@pytest.fixture(scope="module")
def small():
    return tr.reduce(tr.load(os.path.join(HERE, "testdata",
                                          "small.xplane.pb")))


def test_recorded_trace_programs(small):
    # no marks: the window is the span of the operations, which starts a
    # few ns after the first program run does, so that run counts ~0.9999
    p = small["programs"]
    assert p["jit_window_step"]["n"] == pytest.approx(3, abs=0.01)
    assert p["jit_enum"]["n"] == pytest.approx(3, abs=0.01)
    assert p["jit_window_step"]["s"] == pytest.approx(174.656e-6, rel=1e-3)
    assert p["jit_enum"]["s"] == pytest.approx(11.411e-6, rel=1e-3)
    # seconds over runs is the time of one run
    w = p["jit_window_step"]
    assert w["s"] / w["n"] == pytest.approx(174.656e-6 / 3, rel=1e-3)


def test_recorded_trace_busy_is_the_union_of_operations(small):
    # the operations run inside the programs and never overlap across them
    s = sum(v["s"] for v in small["programs"].values())
    assert 0.9 * s < small["busy_s"] <= s * 1.001
    assert small["idle_share"] == pytest.approx(
        1 - small["busy_s"] / small["window_s"])
    top = small["device_ops"][0]
    assert top[0].startswith("jit_window_step/")


def test_recorded_trace_idle_is_the_hosts_sleep(small):
    idle = small["idle_by_host"]
    # two 5 ms sleeps fall between the first and last device operation
    assert idle["bench.idle"] > 0.010
    assert idle["bench.idle"] > 0.9 * sum(idle.values())
