"""Root conftest: make the src-layout package importable without installation,
so a bare ``python -m pytest -x -q`` works (no ``PYTHONPATH=src`` needed).

Also forces two simulated host devices (before any jax import — conftest is
loaded first) so the tensor-parallel serve tests (``tests/test_serve_tp.py``)
can build a 2-way "model" mesh in-process. Single-device suites are unaffected:
their arrays live on device 0 and the computations are identical. A caller who
already set ``XLA_FLAGS`` wins (the TP tests then skip if fewer than 2 devices
come up); the subprocess-based multi-device tests override it themselves."""
import os
import sys

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=2")

_SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)


def count_syncs(monkeypatch, fn):
    """Run ``fn`` with jax.device_get / jax.block_until_ready instrumented;
    returns (number of host syncs, fn's result). Shared by the host-sync-
    budget tests (window / overlap / speculative suites) so the counting
    methodology cannot silently diverge between them."""
    import jax

    counts = {"n": 0}
    real_get, real_block = jax.device_get, jax.block_until_ready

    def counting_get(x):
        counts["n"] += 1
        return real_get(x)

    def counting_block(x):
        counts["n"] += 1
        return real_block(x)

    monkeypatch.setattr(jax, "device_get", counting_get)
    monkeypatch.setattr(jax, "block_until_ready", counting_block)
    try:
        result = fn()
    finally:
        monkeypatch.setattr(jax, "device_get", real_get)
        monkeypatch.setattr(jax, "block_until_ready", real_block)
    return counts["n"], result


_REFERENCE_PREFILLS: dict = {}


def greedy_reference(cfg, params, requests, max_len, eos_id=None):
    """Greedy token streams that depend on no serving engine: for each
    request, ``make_cache_prefill`` over the prompt plus the tokens so far,
    then argmax — until ``max_new_tokens`` or ``eos_id``. Returns
    ``{request id: tokens}``; every engine test compares against this."""
    import numpy as np

    from repro.launch.steps import make_cache_prefill
    from repro.serve.engine import SERVE_PROBES

    key = (id(cfg), max_len)
    if key not in _REFERENCE_PREFILLS:
        # the fused prefill compiles once per (model, capacity); keep cfg
        # alive so its id cannot be reused by another config
        _REFERENCE_PREFILLS[key] = (cfg, make_cache_prefill(
            cfg, SERVE_PROBES, fused=True))
    prefill = _REFERENCE_PREFILLS[key][1]
    out = {}
    for req in requests:
        toks: list[int] = []
        while len(toks) < req.max_new_tokens:
            seq = np.asarray([list(req.prompt) + toks], np.int32)
            logits, _, word = prefill(params, seq, max_len)
            assert int(word) == 0, f"reference prefill faulted: {int(word)}"
            toks.append(int(np.argmax(np.asarray(logits)[0, -1])))
            if eos_id is not None and toks[-1] == eos_id:
                break
        out[req.id] = tuple(toks)
    return out
