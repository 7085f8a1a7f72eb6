"""Device-side error channel — the TPU-native adaptation of the black channel.

XLA SPMD programs cannot take per-rank control-flow decisions at runtime, and a
compiled step cannot throw. The paper's contract — *every misbehaviour becomes an
exception at the wait* — is preserved by inverting the mechanism:

1. every jitted step computes a 32-bit **error word** (the
   :class:`~repro.core.errors.ErrorCode` lattice) from cheap probes over loss /
   grads / states (see ``core/detect.py`` and the ``fault_probe`` Pallas kernel);
2. the word is reduced with ``max``/``or`` *inside* the step. Because probes reduce
   over arrays that are already sharded, XLA folds this into the collectives the step
   performs anyway — the channel costs 4 bytes. This is the in-band analogue of the
   pre-posted ``err_req``: it is always armed, and every rank observes any rank's
   error at the step boundary (one step of latency instead of one ``Waitany``);
3. the host wraps the dispatched outputs in a :class:`DeviceFuture`. ``wait()``
   blocks on the error word *only* (JAX async dispatch keeps the rest in flight) and
   raises the paper's exception taxonomy.

For per-rank attribution the paper's enumeration algorithm (§III-B: scan → index,
bcast → count, allreduce(max) → table) is ported 1:1 to a ``shard_map`` program:
``_scan_sum`` is a log-depth Hillis–Steele inclusive scan over ``ppermute`` (the
ICI-torus-native way to run ``MPI_Scan``), the count uses ``psum`` (numerically
identical to the paper's bcast-of-last-scan-entry, but O(log n) on the torus), and
the table reduction is ``pmax`` — exactly the paper's ``MPI_Allreduce(MPI_MAX)``.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .errors import (
    CommCorruptedError,
    ErrorCode,
    PropagatedError,
    RankError,
    TimeoutError_,
    strip_codes,
)

# static capacity of the device-side (rank, code) table; errors beyond this are
# still reported through the combined word, only unattributed.
MAX_ERRORS = 8

WORD_DTYPE = jnp.uint32


def combine_words(*words: jax.Array) -> jax.Array:
    """Bitwise-or fold of error words (associative, commutative, idempotent)."""
    out = jnp.asarray(0, WORD_DTYPE)
    for w in words:
        out = out | w.astype(WORD_DTYPE)
    return out


# --------------------------------------------------------------------- enumeration
def _scan_sum(x: jax.Array, axis_name: str, n: int) -> jax.Array:
    """Inclusive prefix-sum over a mesh axis (paper's ``MPI_Scan(MPI_SUM)``).

    Hillis–Steele over ``ppermute``: ceil(log2 n) collective-permute steps, each
    moving 4 bytes per link — the torus-native scan.
    """
    idx = jax.lax.axis_index(axis_name)
    offset = 1
    while offset < n:
        shifted = jax.lax.ppermute(
            x, axis_name, [(i, i + offset) for i in range(n - offset)])
        x = jnp.where(idx >= offset, x + shifted, x)
        offset *= 2
    return x


def enumerate_errors_ref(words: jax.Array, max_errors: int = MAX_ERRORS):
    """Pure-jnp oracle of the enumeration algorithm (single array of per-rank words).

    Returns ``(count, table)`` with ``table[i] = (rank, code)`` for the i-th failed
    rank in rank order; rows beyond ``count`` are zero.
    """
    words = words.astype(WORD_DTYPE)
    n = words.shape[0]
    failed = (words != 0).astype(jnp.int32)
    idx = jnp.cumsum(failed) - 1                      # index per failed rank
    count = jnp.sum(failed)
    table = jnp.zeros((max_errors, 2), WORD_DTYPE)
    ranks = jnp.arange(n, dtype=WORD_DTYPE)

    def body(i, tab):
        write = (failed[i] == 1) & (idx[i] < max_errors)
        row = jnp.stack([ranks[i], words[i]])
        return jnp.where(write, tab.at[idx[i]].set(row), tab)

    table = jax.lax.fori_loop(0, n, body, table)
    return count, table


def enumeration_shard_body(word: jax.Array, *, axis_name: str, n: int,
                           max_errors: int = MAX_ERRORS):
    """Per-shard body of the paper's enumeration, to be called inside ``shard_map``.

    ``word`` is this shard's scalar error word. Returns replicated
    ``(count, table)`` on every shard.
    """
    word = word.astype(WORD_DTYPE)
    failed = (word != 0).astype(jnp.int32)
    # paper: MPI_Scan(MPI_SUM) assigns every failed rank an index
    incl = _scan_sum(failed, axis_name, n)
    my_idx = incl - 1
    # paper: count via bcast of the last rank's scan value; psum(failed) is the same
    # number and O(log n) on the torus instead of a root broadcast.
    count = jax.lax.psum(failed, axis_name)
    rank = jax.lax.axis_index(axis_name).astype(WORD_DTYPE)
    table = jnp.zeros((max_errors, 2), WORD_DTYPE)
    write = (failed == 1) & (my_idx < max_errors)
    row = jnp.stack([rank, word])
    table = jnp.where(write, table.at[jnp.maximum(my_idx, 0)].set(row), table)
    # paper: MPI_Allreduce(MPI_MAX) over the zero-initialised table
    table = jax.lax.pmax(table, axis_name)
    return count, table


def make_enumerate_fn(mesh: jax.sharding.Mesh, axis_name: str,
                      max_errors: int = MAX_ERRORS):
    """Build a jitted ``words -> (count, table)`` over one mesh axis.

    ``words`` must be a length-``mesh.shape[axis_name]`` vector sharded over
    ``axis_name``.
    """
    from jax.sharding import PartitionSpec as P

    n = mesh.shape[axis_name]

    def body(words):
        count, table = enumeration_shard_body(
            words[0], axis_name=axis_name, n=n, max_errors=max_errors)
        return count[None], table[None]

    mapped = jax.shard_map(body, mesh=mesh, in_specs=P(axis_name),
                       out_specs=(P(axis_name), P(axis_name, None, None)))

    @jax.jit
    def run(words):
        counts, tables = mapped(words)
        return counts[0], tables[0]

    return run


def decode_table(count: int, table: np.ndarray) -> list[RankError]:
    out = []
    for i in range(min(int(count), table.shape[0])):
        out.append(RankError(rank=int(table[i, 0]), code=int(table[i, 1])))
    return out


# -------------------------------------------------------------------- DeviceFuture
@dataclass
class DeviceFuture:
    """Future over a dispatched jitted step (the JAX analogue of paper's ``Future``).

    ``outputs`` stay asynchronous; ``wait`` synchronises on the 4-byte error word
    (plus the optional enumeration table) and converts it to the paper's exceptions.

    **Window semantics** (decode windows,
    ``launch.steps.make_prefill_decode_window``):
    a future may cover K deferred steps at once. ``word`` is then the OR over
    the whole window — checked once per K tokens, not per token — and
    ``history`` holds the ``(K, ranks)`` per-step per-rank word matrix so that
    on a fault :meth:`fault_steps` attributes it to its exact ``(step, rank)``:
    everything before the first faulting step is a clean, committable prefix,
    which is what keeps deterministic greedy replay (LFLR) bit-exact from the
    last committed boundary.
    """

    outputs: Any
    word: jax.Array
    count: Optional[jax.Array] = None
    table: Optional[jax.Array] = None
    history: Optional[jax.Array] = None   # (K, ranks) per-step word history
    _waited: bool = False

    def wait(self, timeout: float | None = None) -> Any:
        if self._waited:
            return self.outputs
        word_arr = self.word
        if timeout is not None:
            deadline = time.monotonic() + timeout
            while not word_arr.is_ready():
                if time.monotonic() > deadline:
                    raise TimeoutError_(f"device step exceeded {timeout}s "
                                        "(straggler watchdog)")
                time.sleep(0.001)
        word = int(jax.device_get(word_arr))
        self._waited = True
        if word == 0:
            return self.outputs
        code = ErrorCode(word)
        if code & ErrorCode.COMM_CORRUPTED:
            raise CommCorruptedError(self._errors(word))
        raise PropagatedError(self._errors(word) or
                              [RankError(rank=-1, code=word)])

    def result(self, timeout: float | None = None) -> Any:
        return self.wait(timeout=timeout)

    def done(self) -> bool:
        """Non-blocking readiness probe on the error word (the paper's
        ``MPI_Test`` analogue): True iff ``wait()`` would return or raise
        without blocking. Lets a serving loop distinguish a device-bound
        pipeline (the window is still computing at retirement) from a
        host-bound one without perturbing async dispatch."""
        return self._waited or self.word.is_ready()

    def fault_steps(self, *, ignore: int = 0) -> Optional[np.ndarray]:
        """Per-rank index of the first faulting window step, or -1 if clean.

        Requires window ``history``; returns an ``(ranks,)`` int array. Tokens
        produced by steps ``< fault_steps()[r]`` on rank/slot ``r`` are a valid
        prefix (their words were zero), so the host commits them and replays
        only from the fault boundary. ``ignore`` masks code bits out before
        the scan — the speculative window passes its attribution-only
        ``DRAFT_REJECT`` lane here, so a speculation miss is never mistaken
        for the first *faulting* step and the clean prefix stays as long as
        the real fault allows.
        """
        if self.history is None:
            return None
        hist = np.asarray(jax.device_get(self.history)).astype(np.uint32)
        hist = strip_codes(hist, ignore)
        bad = hist != 0
        return np.where(bad.any(axis=0), bad.argmax(axis=0), -1).astype(np.int64)

    def fault_codes(self, *, ignore: int = 0) -> Optional[np.ndarray]:
        """Per-rank OR of the window history — the combined fault class each
        rank/slot latched, or 0 if clean. Unlike the enumeration table (whose
        capacity is ``max_errors``), this never truncates, so a host that must
        pick a per-slot recovery lane (e.g. the paged-KV replica separating
        ``PAGE_FAULT`` ledger repairs from ``STATE_FAULT`` recomputes) can
        attribute every slot even under a burst of simultaneous faults — and,
        with the default ``ignore=0``, distinguish speculation misses
        (``DRAFT_REJECT``) from real faults in the same readback.
        Requires window ``history``; returns a ``(ranks,)`` uint32 array.
        """
        if self.history is None:
            return None
        hist = np.asarray(jax.device_get(self.history)).astype(np.uint32)
        hist = strip_codes(hist, ignore)
        out = np.zeros(hist.shape[1], np.uint32)
        for row in hist:
            out |= row
        return out

    def _errors(self, word: int) -> list[RankError]:
        if self.count is None or self.table is None:
            return []
        cnt = int(jax.device_get(self.count))
        tab = np.asarray(jax.device_get(self.table))
        errs = decode_table(cnt, tab)
        if not errs and word:
            errs = [RankError(rank=-1, code=word)]
        return errs
