"""bench/spans.py on hand-written tracer events with known answers: two
profiled steps (one retiring a window, one retiring none), one step before
the profiled span, and the dispatch and commit counters of three windows."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import spans  # noqa: E402


def X(name, ts_s, dur_ms, pid=0, **args):
    return {"name": name, "cat": "phase", "ph": "X", "ts": ts_s * 1e6,
            "dur": dur_ms * 1e3, "pid": pid, "tid": 1 << 20,
            "args": args or None}


EVENTS = [
    # before the profiled span: not counted
    X("serve.step", 9.0, 50.0, step=1),
    X("serve.dispatch", 9.001, 1.0, window=1, slots=4, lanes=4,
      prefill_lanes=4, prompt_tokens=32),
    X("serve.wait", 9.002, 45.0, window=0, ready=False),
    X("serve.commit", 9.047, 1.0, window=0, committed=30, discarded=2),
    # profiled: 100 ms step, 90 ms of it waiting on window 1
    X("serve.step", 10.0, 100.0, step=2),
    X("serve.admit", 10.0, 1.0, step=2),
    X("serve.dispatch", 10.001, 2.0, window=2, slots=4, lanes=3,
      prefill_lanes=1, prompt_tokens=8),
    X("serve.wait", 10.003, 90.0, window=1, ready=False),
    X("serve.commit", 10.093, 3.0, window=1, committed=0, discarded=0),
    # profiled: 20 ms step, 14 ms waiting on window 2
    X("serve.step", 10.2, 20.0, step=3),
    X("serve.dispatch", 10.201, 2.0, window=3, slots=4, lanes=2,
      prefill_lanes=0, prompt_tokens=0),
    X("serve.wait", 10.203, 14.0, window=2, ready=True),
    X("serve.commit", 10.217, 1.0, window=2, committed=20, discarded=4),
    # profiled, retires nothing: its host time counts, its windows do not
    X("serve.step", 10.3, 5.0, step=4),
    # after the profiled span
    X("serve.step", 11.5, 30.0, step=5),
    X("serve.wait", 11.5, 25.0, window=3, ready=False),
    X("serve.commit", 11.525, 1.0, window=3, committed=6, discarded=0),
    # another replica's instant, and an instant of the same name: ignored
    {"name": "serve.step", "ph": "i", "ts": 10.5e6, "pid": 0, "tid": 0},
]


def art(events=EVENTS, host=(10.0, 11.0)):
    return {"tracer": events, "profile_host": host}


def test_host_ms_per_window_is_step_less_its_waits_per_retired_window():
    # (100 - 90) + (20 - 14) + 5 ms over the two windows retired
    assert spans.host_ms_per_window(art()) == pytest.approx(21.0 / 2)


def test_occupancy_is_lanes_over_slots_of_profiled_dispatches():
    # windows 2 and 3: (3 + 2) / (4 + 4)
    assert spans.occupancy(art()) == pytest.approx(62.5)


def test_prefill_share_and_useful_tokens_pair_dispatch_with_commit():
    # windows 2 (8 fed, 20 committed) and 3 (0 fed, 6 committed after the
    # span); window 1 was dispatched before the span
    assert spans.window_tokens(art()) == [(8, 20), (0, 6)]
    assert spans.prefill_share(art()) == pytest.approx(8 / 34 * 100)
    assert spans.useful_tokens(art()) == pytest.approx(34 / 2)


def test_a_window_not_retired_is_left_out():
    evs = [e for e in EVENTS if not (e["name"] == "serve.commit"
                                     and e["args"]["window"] == 3)]
    assert spans.window_tokens(art(evs)) == [(8, 20)]
    assert spans.useful_tokens(art(evs)) == pytest.approx(28.0)


def test_pids_keep_replicas_apart():
    other = [dict(e, pid=1) for e in EVENTS if e.get("ph") == "X"]
    both = art(EVENTS + other)
    assert spans.host_ms_per_window(both) == pytest.approx(42.0 / 4)
    assert spans.window_tokens(both) == [(8, 20), (0, 6)] * 2


@pytest.mark.parametrize("reader", [
    spans.host_ms_per_window, spans.occupancy, spans.prefill_share,
    spans.useful_tokens])
def test_nothing_to_read_gives_none(reader):
    # a program without the phase spans (other events only), no profiled
    # span, or a profiled span that holds none of the spans
    plain = [{"name": "window", "ph": "X", "ts": 10.1e6, "dur": 1e3,
              "pid": 0, "tid": 0, "args": {"window": 1}}]
    assert reader(art(plain)) is None
    assert reader(art(host=(None, None))) is None
    assert reader(art(host=(20.0, 21.0))) is None
