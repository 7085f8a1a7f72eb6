"""Speculative decode windows (`Replica(speculate=True)`): token-bit-exact
draft-and-verify vs the overlap engine on steady, faulted and paged traffic;
per-slot variable commit (EOS inside an accepted draft run, deadline expiry
mid-window); the attribution-only DRAFT_REJECT lane never triggering
recovery; LFLR after a real fault mid-speculation committing no stale draft
tokens; acceptance-rate metrics; and the host-sync budget staying O(steps/K).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import count_syncs

from repro.configs import smoke_config
from repro.core.device_channel import DeviceFuture
from repro.core.errors import ATTRIBUTION_ONLY, ErrorCode
from repro.launch.steps import make_speculative_decode_window
from repro.models import build_model
from repro.serve import EXPIRED, OK, EngineConfig, Replica, Request, ServeGroup
from repro.serve.replica import make_window_enum_fn

MAX_LEN = 64
D = 3           # draft_len for the suite
K = 8


@pytest.fixture(scope="module")
def env():
    cfg = smoke_config("qwen3-1.7b")
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    return cfg, params


def _replica(env, *, speculate, **kw):
    cfg, params = env
    conf = {k: kw.pop(k) for k in list(kw) if k in EngineConfig.__dataclass_fields__}
    conf.setdefault("num_slots", 2)
    conf.setdefault("max_len", MAX_LEN)
    conf.setdefault("max_request_retries", 6)
    return Replica(cfg, params=params,
                   config=EngineConfig(window=K, overlap=True,
                                       speculate=speculate, draft_len=D,
                                       draft_layers=1, **conf), **kw)


def _requests(n, max_new=16, prompt_len=9):
    return [Request(id=i, prompt=tuple(5 + i + j for j in range(prompt_len)),
                    max_new_tokens=max_new) for i in range(n)]


def _serve_all(rep, reqs, inject_first_eligible=False):
    for r in reqs:
        assert rep.submit(r) is None
    out, steps, injected = {}, 0, 0
    while not rep.idle():
        if inject_first_eligible and not injected:
            # poison a *decoding* lane (a fresh chunk lane's reset would
            # silently wipe the injection before any window consumes it)
            eligible = [i for i in rep.sched.active_slots()
                        if rep.sched.slots[i].pending is None]
            if eligible and rep.inject_state_fault(eligible[0]) is not None:
                injected += 1
        for resp in rep.step():
            out[resp.id] = resp
        steps += 1
        assert steps < 2000
    if inject_first_eligible:
        assert injected == 1, "fault injection never found a decoding lane"
    return out


# ------------------------------------------------------------- bit-exactness
def test_spec_bit_exact_steady(env):
    """Every emitted token is a full-model argmax, so draft-and-verify must
    be invisible in the stream — including backfill chains (5 requests over
    2 slots)."""
    base = _serve_all(_replica(env, speculate=False), _requests(5))
    rep = _replica(env, speculate=True)
    got = _serve_all(rep, _requests(5))
    assert sorted(got) == sorted(base)
    for i in base:
        assert got[i].status == OK
        assert got[i].tokens == base[i].tokens, i
    assert rep.metrics.windows > 0


def test_spec_bit_exact_faulted_lflr(env):
    """A real fault mid-speculation recovers via LFLR bit-exactly: the
    poisoned lane's stream is identical to the overlap engine under the same
    injection, and the fault surfaces as a real (non-DRAFT_REJECT) class."""
    base = _serve_all(_replica(env, speculate=False), _requests(5),
                      inject_first_eligible=True)
    rep = _replica(env, speculate=True)
    got = _serve_all(rep, _requests(5), inject_first_eligible=True)
    for i in base:
        assert got[i].status == OK
        assert got[i].tokens == base[i].tokens, i
    counts = rep.metrics.fault_counts()
    assert counts, "injected fault was never detected"
    assert "DRAFT_REJECT" not in counts


def test_spec_paged_bit_exact(env):
    """Speculation composes with the paged KV pool: same stream, ledger
    consistent, steady and faulted."""
    for inject in (False, True):
        base = _serve_all(_replica(env, speculate=False), _requests(5),
                          inject_first_eligible=inject)
        rep = _replica(env, speculate=True, paged=True, page_size=16)
        got = _serve_all(rep, _requests(5), inject_first_eligible=inject)
        for i in base:
            assert got[i].tokens == base[i].tokens, (inject, i)
        rep.alloc.check()


# ------------------------------------------------------- variable-commit path
def test_eos_inside_accepted_draft_run(env):
    """A request whose EOS lands *inside* an accepted draft run must stop at
    exactly the same token as the plain engine (commit_block checks token by
    token), with the trailing accepts discarded, not committed."""
    cfg, params = env
    # find an eos token that actually appears mid-stream in the clean run
    probe = _serve_all(_replica(env, speculate=False), _requests(2, max_new=24))
    stream = probe[0].tokens
    eos = stream[min(5, len(stream) - 2)]

    def serve(speculate):
        rep = _replica(env, speculate=speculate, eos_id=int(eos))
        return rep, _serve_all(rep, _requests(2, max_new=24))

    _, base = serve(False)
    rep, got = serve(True)
    for i in base:
        assert got[i].tokens == base[i].tokens, i
        assert got[i].status == base[i].status == OK
    # at least one lane must actually have stopped early on EOS
    assert any(len(r.tokens) < 24 for r in got.values())
    assert rep.metrics.discarded_tokens > 0


def test_deadline_expiry_mid_window(env):
    """A deadline passing mid-window evicts the lane at the window boundary;
    its already-emitted block is discarded wholesale, co-slot lanes are
    unaffected and the expired request is answered EXPIRED."""
    t = {"now": 0.0}
    rep = _replica(env, speculate=True, clock=lambda: t["now"])
    reqs = _requests(2, max_new=40)
    doomed = Request(id=99, prompt=(7, 8, 9), max_new_tokens=40, deadline=2.0)
    for r in [doomed] + reqs:      # doomed admitted first: it dies in a slot
        assert rep.submit(r) is None
    out, steps = {}, 0
    while not rep.idle():
        t["now"] += 1.0            # two cycles in, the deadline has passed
        for resp in rep.step():
            out[resp.id] = resp
        steps += 1
        assert steps < 2000
    assert out[99].status == EXPIRED
    assert "mid-decode" in out[99].detail   # evicted from a live lane, not
    assert len(out[99].tokens) < 40         # the queue — block discarded
    assert out[0].status == OK and out[1].status == OK
    assert len(out[0].tokens) == 40 and len(out[1].tokens) == 40


def test_variable_commit_accounting(env):
    """Committed tokens must equal the sum of response streams, and the
    window ledger must balance: every emitted token is either committed or
    discarded, never duplicated. tokens_per_step counts per *dispatched*
    step (all slots), so the speculation signal is beating the plain engine
    at the SAME slot count on the same traffic — not merely exceeding 1."""
    plain = _replica(env, speculate=False)
    _serve_all(plain, _requests(4, max_new=12))
    rep = _replica(env, speculate=True)
    got = _serve_all(rep, _requests(4, max_new=12))
    m = rep.metrics
    assert m.decode_tokens == sum(len(r.tokens) for r in got.values())
    assert m.decode_steps == m.windows * K
    assert m.discarded_tokens >= 0
    assert m.tokens_per_step() > plain.metrics.tokens_per_step()


# ------------------------------------------------ DRAFT_REJECT attribution
def test_draft_reject_is_masked_from_fault_word():
    """A window whose only events are speculation misses must wait() clean:
    the enum strips DRAFT_REJECT from the combined word and the table, while
    the history keeps it for attribution."""
    enum = make_window_enum_fn(2, int(ErrorCode.DRAFT_REJECT))
    hist = np.zeros((K, 2), np.uint32)
    hist[3, 1] = int(ErrorCode.DRAFT_REJECT)
    combined, count, table, out_hist = enum(jnp.asarray(hist),
                                            jnp.ones((2,), jnp.uint32))
    assert int(combined) == 0 and int(count) == 0
    assert int(np.asarray(out_hist)[3, 1]) == int(ErrorCode.DRAFT_REJECT)
    fut = DeviceFuture(outputs="ok", word=combined, count=count, table=table,
                       history=out_hist)
    assert fut.wait() == "ok"          # never raises: attribution only
    # fault_steps with the mask sees a clean window; without it, the miss
    # is attributable to its exact (step, slot)
    assert list(fut.fault_steps(ignore=int(ATTRIBUTION_ONLY))) == [-1, -1]
    assert list(fut.fault_steps()) == [-1, 3]
    codes = fut.fault_codes()
    assert int(codes[1]) == int(ErrorCode.DRAFT_REJECT)


def test_draft_reject_does_not_truncate_clean_prefix():
    """A real fault behind rejected drafts: the committable prefix must run
    up to the *fault* step, not stop at the first speculation miss."""
    enum = make_window_enum_fn(1, int(ErrorCode.DRAFT_REJECT))
    hist = np.zeros((K, 1), np.uint32)
    hist[1, 0] = int(ErrorCode.DRAFT_REJECT)
    hist[5, 0] = int(ErrorCode.NONFINITE_LOSS) | int(ErrorCode.DRAFT_REJECT)
    combined, count, table, out_hist = enum(jnp.asarray(hist),
                                            jnp.ones((1,), jnp.uint32))
    assert int(combined) == int(ErrorCode.NONFINITE_LOSS)
    fut = DeviceFuture(outputs=None, word=combined, count=count, table=table,
                       history=out_hist)
    steps = fut.fault_steps(ignore=int(ErrorCode.DRAFT_REJECT))
    assert list(steps) == [5]
    codes = fut.fault_codes(ignore=int(ErrorCode.DRAFT_REJECT))
    assert int(codes[0]) == int(ErrorCode.NONFINITE_LOSS)


def test_spec_steady_run_never_recovers(env):
    """Steady speculative traffic must not consume retries or record faults
    — rejected drafts are expected events, not recoverable errors."""
    rep = _replica(env, speculate=True)
    got = _serve_all(rep, _requests(4, max_new=16))
    assert rep.metrics.faults == []
    assert sum(r.retries for r in got.values()) == 0


def test_real_fault_commits_no_stale_draft_tokens(env):
    """Tokens from the faulted step onward never commit: after LFLR the
    replayed stream is the deterministic greedy one, so the total stream is
    exactly the clean stream — a stale draft token would break equality."""
    clean = _serve_all(_replica(env, speculate=False), _requests(3))
    rep = _replica(env, speculate=True)
    got = _serve_all(rep, _requests(3), inject_first_eligible=True)
    for i in clean:
        assert got[i].tokens == clean[i].tokens, i


# ------------------------------------------------------------------- metrics
def test_acceptance_rate_metrics(env):
    rep = _replica(env, speculate=True)
    _serve_all(rep, _requests(4, max_new=16))
    m = rep.metrics
    assert m.draft_tokens > 0
    assert 0 <= m.accepted_draft_tokens <= m.draft_tokens
    assert 0.0 < m.acceptance_rate() <= 1.0
    assert m.acceptance_rate() == m.accepted_draft_tokens / m.draft_tokens
    per_slot = m.acceptance_rate_per_slot()
    assert per_slot and set(per_slot) <= {0, 1}
    assert all(0.0 <= v <= 1.0 for v in per_slot.values())
    # the global counters must equal the per-slot cells they were recorded
    # from — an independent reconstruction, not the summary's own formula
    cells = m._spec_per_slot
    assert m.draft_tokens == sum(d for d, _ in cells.values())
    assert m.accepted_draft_tokens == sum(a for _, a in cells.values())
    # accepted drafts can never exceed what a window can emit beyond its
    # forced rows: every commit this run came through windows, so committed
    # tokens bound accepted drafts from above
    assert m.accepted_draft_tokens <= m.decode_tokens + m.discarded_tokens
    s = m.summary()
    for key in ("draft_tokens", "accepted_draft_tokens",
                "rejected_draft_tokens", "acceptance_rate",
                "acceptance_rate_per_slot", "tokens_per_step"):
        assert key in s
    assert s["rejected_draft_tokens"] == (m.draft_tokens
                                          - m.accepted_draft_tokens)


# ---------------------------------------------------------- host-sync budget
def test_host_sync_budget(env, monkeypatch):
    """Speculation adds no per-token host traffic: the accepted counts ride
    the existing one-readback-per-window (word + token/count block), so syncs
    stay O(steps / K) — NOT O(tokens)."""

    def run():
        rep = _replica(env, speculate=True)
        return rep, _serve_all(rep, _requests(6, max_new=16))

    run()                                   # warm compiles
    syncs, (rep, out) = count_syncs(monkeypatch, run)
    assert all(r.status == OK for r in out.values())
    m = rep.metrics
    assert syncs <= 2 * m.windows + 4, (syncs, m.windows)
    # and the window count itself reflects multi-token commits: far fewer
    # windows than committed tokens / K
    assert m.windows * K < m.decode_tokens * 0.9


# ------------------------------------------------------------ configuration
def test_spec_validation(env):
    cfg, params = env
    with pytest.raises(ValueError, match="full-attention"):
        make_speculative_decode_window(smoke_config("recurrentgemma-2b"),
                                       window=8, draft_len=2, draft_layers=1)
    with pytest.raises(ValueError, match="draft_layers"):
        make_speculative_decode_window(cfg, window=8, draft_len=2,
                                       draft_layers=cfg.num_layers)
    with pytest.raises(ValueError, match="draft_len"):
        make_speculative_decode_window(cfg, window=8, draft_len=0,
                                       draft_layers=1)
    rec = smoke_config("recurrentgemma-2b")
    with pytest.raises(ValueError, match="full-attention"):
        Replica(rec, config=EngineConfig(window=8, speculate=True))


def test_spec_serve_group(env):
    """ServeGroup threads speculation through shared jitted programs: the
    fleet serves to completion with every response OK and acceptance > 0."""
    cfg, _ = env
    group = ServeGroup(cfg, nranks=2,
                       config=EngineConfig(num_slots=2, max_len=MAX_LEN,
                                           window=K, speculate=True,
                                           draft_len=D, draft_layers=1))
    reqs = _requests(6, max_new=10)
    result = group.serve(reqs)
    assert sorted(result.responses) == [r.id for r in reqs]
    assert all(r.ok for r in result.responses.values())
    accepted = sum(r.metrics.accepted_draft_tokens
                   for r in (result.report(i) for i in range(2)) if r)
    assert accepted > 0
