"""``chip_smoke.py`` on the CPU: the rehearsal passes end to end and ends in
the one-line JSON result; the default mode refuses to run without a TPU."""
import importlib.util
import json
import pathlib

import jax
import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def chip_smoke():
    from jax.experimental.compilation_cache import compilation_cache as cc

    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    # main() turns the compile cache on; leave this worker as it was
    before = jax.config.jax_compilation_cache_dir
    yield mod
    jax.config.update("jax_compilation_cache_dir", before)
    cc.reset_cache()


def _last_line(out: str) -> dict:
    lines = out.strip().splitlines()
    assert lines, "no output"
    return json.loads(lines[-1])


def test_rehearsal_ends_in_one_json_line(chip_smoke, capsys):
    assert jax.devices()[0].platform == "cpu"
    assert chip_smoke.main(["--rehearse"]) == 0
    out = capsys.readouterr().out
    result = _last_line(out)
    assert set(result) == {"ok", "device"}
    assert result["ok"] is True
    assert set(result["device"]) == {"platform", "kind", "count"}
    assert result["device"] == {"platform": "cpu",
                                "kind": jax.devices()[0].device_kind,
                                "count": len(jax.devices())}
    for phase in ("serve:", "fault:", "reference:", "compile:"):
        assert any(line.startswith(phase) for line in out.splitlines()), phase


@pytest.mark.parametrize("env_dir", [None, "/cache/from/env"])
def test_compile_cache_dir(monkeypatch, env_dir):
    """JAX_COMPILATION_CACHE_DIR wins and is left to JAX; otherwise the
    cache is the fixed <checkout>/.jax_cache."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    from repro.launch.compile_cache import enable_compile_cache

    before = jax.config.jax_compilation_cache_dir
    if env_dir:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        got = enable_compile_cache()
        if env_dir:
            assert got == env_dir
            assert jax.config.jax_compilation_cache_dir == before
        else:
            assert got == str(ROOT / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        cc.reset_cache()


def test_default_mode_refuses_without_a_tpu(chip_smoke, capsys):
    assert jax.devices()[0].platform != "tpu"
    assert chip_smoke.main([]) != 0
    captured = capsys.readouterr()
    assert '"ok"' not in captured.out
    assert "no TPU" in captured.err
