"""Device-channel tests: error-word lattice, enumeration (ref + shard_map port),
DeviceFuture semantics, probes, in-step fault injection."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    CommCorruptedError,
    DeviceFuture,
    ErrorCode,
    PropagatedError,
    combine_words,
    decode_table,
    enumerate_errors_ref,
)
from repro.core.detect import ProbeConfig, grad_probe, loss_probe, data_probe, step_probe
from repro.core.faults import (
    INJ_NAN_GRAD,
    INJ_NAN_LOSS,
    inject_grads,
    inject_loss,
)


def test_enumerate_ref_basic():
    words = jnp.array([0, 5, 0, 9, 0, 0, 3, 0], dtype=jnp.uint32)
    count, table = enumerate_errors_ref(words)
    errs = decode_table(int(count), np.asarray(table))
    assert [(e.rank, e.code) for e in errs] == [(1, 5), (3, 9), (6, 3)]


def test_enumerate_ref_empty_and_full():
    words = jnp.zeros(16, jnp.uint32)
    count, table = enumerate_errors_ref(words)
    assert int(count) == 0
    assert np.all(np.asarray(table) == 0)

    words = jnp.full(4, 7, jnp.uint32)
    count, table = enumerate_errors_ref(words)
    errs = decode_table(int(count), np.asarray(table))
    assert [(e.rank, e.code) for e in errs] == [(0, 7), (1, 7), (2, 7), (3, 7)]


def test_device_future_raises_propagated():
    word = jnp.uint32(int(ErrorCode.NONFINITE_LOSS))
    fut = DeviceFuture(outputs="state", word=word)
    with pytest.raises(PropagatedError) as ei:
        fut.wait()
    assert ei.value.combined_code & ErrorCode.NONFINITE_LOSS


def test_device_future_ok_passthrough():
    fut = DeviceFuture(outputs={"x": 1}, word=jnp.uint32(0))
    assert fut.wait() == {"x": 1}
    assert fut.result() == {"x": 1}  # idempotent


def test_device_future_window_fault_steps():
    """Window semantics: the (K, slots) history attributes a fault to its
    exact (step, slot); clean slots report -1."""
    hist = jnp.array([[0, 0, 0],
                      [0, 9, 0],
                      [3, 9, 0]], dtype=jnp.uint32)     # (K=3, slots=3)
    word = jnp.uint32(3 | 9)
    fut = DeviceFuture(outputs=None, word=word, history=hist)
    np.testing.assert_array_equal(fut.fault_steps(), [2, 1, -1])
    with pytest.raises(PropagatedError):
        fut.wait()
    # no history → no step attribution (per-step futures)
    assert DeviceFuture(outputs=None, word=word).fault_steps() is None


def test_device_future_corrupted():
    word = jnp.uint32(int(ErrorCode.COMM_CORRUPTED))
    fut = DeviceFuture(outputs=None, word=word)
    with pytest.raises(CommCorruptedError):
        fut.wait()


def test_loss_probe():
    cfg = ProbeConfig(loss_divergence_threshold=100.0)
    assert int(loss_probe(jnp.float32(1.0), cfg)) == 0
    assert int(loss_probe(jnp.float32(jnp.nan), cfg)) & int(ErrorCode.NONFINITE_LOSS)
    assert int(loss_probe(jnp.float32(jnp.inf), cfg)) & int(ErrorCode.NONFINITE_LOSS)
    assert int(loss_probe(jnp.float32(1e4), cfg)) & int(ErrorCode.DIVERGENCE)


def test_grad_probe_kernel_vs_ref():
    cfg = ProbeConfig(overflow_threshold=10.0)
    clean = {"a": jnp.ones((64, 130)), "b": jnp.zeros((7,))}
    assert int(grad_probe(clean, cfg)) == 0
    dirty = {"a": jnp.ones((64, 130)).at[3, 5].set(jnp.nan), "b": jnp.zeros((7,))}
    assert int(grad_probe(dirty, cfg)) & int(ErrorCode.NONFINITE_GRAD)
    hot = {"a": jnp.ones((64, 130)).at[0, 0].set(100.0), "b": jnp.zeros((7,))}
    assert int(grad_probe(hot, cfg)) & int(ErrorCode.OVERFLOW)


def test_data_probe():
    ok = jnp.array([[1, 2], [3, 4]], dtype=jnp.int32)
    assert int(data_probe(ok, vocab_size=10)) == 0
    bad = jnp.array([[1, -2], [3, 4]], dtype=jnp.int32)
    assert int(data_probe(bad, vocab_size=10)) & int(ErrorCode.DATA_FAULT)
    big = jnp.array([[1, 2], [3, 40]], dtype=jnp.int32)
    assert int(data_probe(big, vocab_size=10)) & int(ErrorCode.DATA_FAULT)


def test_injection_inside_jit():
    @jax.jit
    def step(x, inject):
        loss = jnp.mean(x)
        loss = inject_loss(loss, inject)
        grads = {"w": x}
        grads = inject_grads(grads, inject)
        word = step_probe(loss, grads, cfg=ProbeConfig())
        return loss, word

    x = jnp.ones((8, 8))
    _, w0 = step(x, jnp.uint32(0))
    assert int(w0) == 0
    _, w1 = step(x, jnp.uint32(INJ_NAN_LOSS))
    assert int(w1) & int(ErrorCode.NONFINITE_LOSS)
    _, w2 = step(x, jnp.uint32(INJ_NAN_GRAD))
    assert int(w2) & int(ErrorCode.NONFINITE_GRAD)


def test_combine_words():
    a = jnp.uint32(int(ErrorCode.NONFINITE_LOSS))
    b = jnp.uint32(int(ErrorCode.OVERFLOW))
    c = combine_words(a, b)
    assert ErrorCode(int(c)) == ErrorCode.NONFINITE_LOSS | ErrorCode.OVERFLOW


_MULTIDEV_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.core import enumerate_errors_ref, make_enumerate_fn
mesh = jax.make_mesh((8,), ("ranks",),
                     axis_types=(jax.sharding.AxisType.Auto,))
run = make_enumerate_fn(mesh, "ranks")
rng = np.random.default_rng(0)
for trial in range(20):
    words = rng.choice([0, 0, 0, 3, 5, 9], size=8).astype(np.uint32)
    words_j = jnp.asarray(words)
    c1, t1 = run(jax.device_put(
        words_j, jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("ranks"))))
    c2, t2 = enumerate_errors_ref(words_j)
    assert int(c1) == int(c2), (trial, int(c1), int(c2))
    np.testing.assert_array_equal(np.asarray(t1), np.asarray(t2))
print("MULTIDEV_OK")
"""


def test_enumeration_shardmap_matches_ref_multidevice():
    """The paper's scan/bcast/allreduce enumeration as a shard_map program over 8
    simulated devices must match the pure-jnp oracle (run in a subprocess so the
    main test process keeps a single CPU device)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    out = subprocess.run([sys.executable, "-c", _MULTIDEV_SCRIPT], cwd="/root/repo",
                         env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "MULTIDEV_OK" in out.stdout
