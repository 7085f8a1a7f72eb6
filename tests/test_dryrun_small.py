"""Small-scale dry-run: the full lower→compile→analyse pipeline on an 8-device
host mesh (subprocess so the main pytest process keeps 1 device)."""
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from repro.configs import smoke_config
from repro.configs.base import ShapeConfig
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import input_specs, make_step_for
from repro.roofline.hlo import estimate_hbm_bytes, parse_collectives

ARCH = "%(arch)s"
cfg = smoke_config(ARCH).replace(dtype="bfloat16")
shape = ShapeConfig("%(kind)s_t", seq_len=64, global_batch=8, kind="%(kind)s")
mesh = make_host_mesh(data=4, model=2)
step = make_step_for(cfg, shape)
args, shardings = input_specs(cfg, shape, mesh)
with mesh:
    lowered = jax.jit(step, in_shardings=shardings).lower(*args)
    compiled = lowered.compile()
cost = compiled.cost_analysis()
mem = compiled.memory_analysis()
hlo = compiled.as_text()
coll = parse_collectives(hlo)
hbm = estimate_hbm_bytes(hlo)
assert cost.get("flops", 0) > 0
assert hbm["total_bytes"] > 0
assert mem.argument_size_in_bytes > 0
print("CELL_OK", ARCH, cost["flops"], int(coll.total_bytes))
"""


def _run(arch: str, kind: str):
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    out = subprocess.run(
        [sys.executable, "-c", _SCRIPT % {"arch": arch, "kind": kind}],
        cwd="/root/repo", env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "CELL_OK" in out.stdout


@pytest.mark.parametrize("arch", ["qwen3-moe-30b-a3b", "recurrentgemma-2b",
                                  "mamba2-2.7b", "hubert-xlarge"])
def test_small_mesh_train_cell(arch):
    _run(arch, "train")


@pytest.mark.parametrize("arch", ["gemma3-1b", "mamba2-2.7b"])
def test_small_mesh_decode_cell(arch):
    _run(arch, "decode")


def test_multipod_small_mesh():
    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
from repro.configs import smoke_config
from repro.configs.base import ShapeConfig
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import input_specs, make_step_for
cfg = smoke_config("qwen3-1.7b").replace(dtype="bfloat16")
shape = ShapeConfig("t", seq_len=32, global_batch=8, kind="train")
mesh = make_host_mesh(data=2, model=2, pod=2)
step = make_step_for(cfg, shape)
args, shardings = input_specs(cfg, shape, mesh)
with mesh:
    compiled = jax.jit(step, in_shardings=shardings).lower(*args).compile()
hlo = compiled.as_text()
assert "all-reduce" in hlo
print("MULTIPOD_OK")
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    out = subprocess.run([sys.executable, "-c", script], cwd="/root/repo",
                         env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "MULTIPOD_OK" in out.stdout
