"""The one traffic generator: a mix file's parameters plus a seed → a plan.

Every seed gets the same set of sizes and arrival gaps, in another order, so
a seed changes which request comes when and what its tokens are, never how
much work a run holds. Sizes are the stratified quantiles of the mix's
distribution (for ``n`` requests, the quantiles at ``(i + 0.5) / n``), and
arrival gaps the stratified quantiles of an exponential, scaled so that the
gaps of a span add up to it exactly.

Mix keys (``bench/traffic/<mix>.json``):

- ``loop``: ``"open"`` (requests due on a schedule, whatever the engine does)
  or ``"closed"`` (``clients`` clients, each sending its next request when
  its last one is answered);
- ``rate_per_s`` (open): arrivals per second; ``clients`` (closed);
- ``warm_s``: traffic offered before the measured window opens, so that the
  window starts with the queue and the slots in their steady state;
- ``prompt`` / ``output``: ``{"median", "sigma", "min", "max"}`` of a
  lognormal length, clipped;
- ``faults`` (optional): ``{"kind": "state", "rate_per_s"}``, soft state
  faults at stratified exponential gaps over the window;
- ``check_tokens``: served tokens the correctness sample holds at least;
- ``drain_s``: how long past the window's close an answer is waited for.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np


@dataclass(frozen=True)
class Item:
    """One request of the plan. ``due`` is in seconds from the window's
    opening (negative in the warm-up); closed-loop items carry ``client``
    and are due when their client is free."""

    rid: int
    prompt: tuple
    max_new: int
    due: float = 0.0
    client: int = -1


@dataclass(frozen=True)
class Plan:
    loop: str
    items: tuple            # open: by due time; closed: by (client, turn)
    faults: tuple           # fault times, seconds from the window's opening
    warm_s: float
    clients: int = 0


def lengths(dist: dict, n: int) -> np.ndarray:
    """The ``n`` stratified quantiles of a clipped lognormal length."""
    nd = NormalDist()
    z = np.asarray([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    vals = np.rint(dist["median"] * np.exp(dist["sigma"] * z))
    return np.clip(vals, dist["min"], dist["max"]).astype(np.int64)


def gaps(n: int, span: float) -> np.ndarray:
    """``n`` stratified exponential gaps adding up to ``span``."""
    g = np.asarray([-math.log(1.0 - (i + 0.5) / n) for i in range(n)])
    return g * (span / g.sum())


def _tokens(rng: np.random.Generator, n: int, vocab: int) -> tuple:
    return tuple(int(t) for t in rng.integers(1, vocab, size=int(n)))


def make_plan(mix: dict, seed: int, seconds: float, vocab: int) -> Plan:
    rng = np.random.default_rng(int(seed))
    warm = float(mix.get("warm_s", 0.0))
    faults = ()
    if mix.get("faults"):
        nf = max(1, round(mix["faults"]["rate_per_s"] * seconds))
        t = np.cumsum(rng.permutation(gaps(nf, seconds)))
        faults = tuple(float(x) for x in t - t[0] * 0.5)
    if mix["loop"] == "open":
        items, rid = [], 0
        for lo, span in ((-warm, warm), (0.0, float(seconds))):
            n = round(mix["rate_per_s"] * span)
            if n == 0:
                continue
            p = rng.permutation(lengths(mix["prompt"], n))
            o = rng.permutation(lengths(mix["output"], n))
            # first arrival half a gap into the span, the rest by the gaps
            t = np.cumsum(rng.permutation(gaps(n, span)))
            t = lo + t - t[0] * 0.5
            for i in range(n):
                items.append(Item(rid, _tokens(rng, p[i], vocab), int(o[i]),
                                  due=float(t[i])))
                rid += 1
        return Plan("open", tuple(items), faults, warm)
    if mix["loop"] == "closed":
        # a Latin square: in every turn the clients together send the whole
        # stratified set once, each client at a seeded offset
        c = int(mix["clients"])
        turns = int(mix.get("turns", 16))
        p = lengths(mix["prompt"], c)
        o = lengths(mix["output"], c)
        po, oo = rng.permutation(c), rng.permutation(c)
        items, rid = [], 0
        for client in range(c):
            for j in range(turns):
                items.append(Item(rid, _tokens(rng, p[(po[client] + j) % c],
                                               vocab),
                                  int(o[(oo[client] + 3 * j) % c]),
                                  client=client))
                rid += 1
        return Plan("closed", tuple(items), faults, warm, clients=c)
    raise ValueError(f"unknown loop {mix['loop']!r}")
