"""Roofline machinery: HLO collective parsing, HBM estimator, term maths."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import SHAPES, get_config
from repro.roofline.analysis import (
    RooflineTerms,
    model_flops_for,
    peaks_for,
)
from repro.roofline.hlo import (
    _shape_bytes,
    estimate_hbm_bytes,
    parse_collectives,
)

SYNTH = """
HloModule test

ENTRY %main (p0: f32[128,256]) -> f32[128,256] {
  %p0 = f32[128,256]{1,0} parameter(0)
  %ar = f32[128,256]{1,0} all-reduce(%p0), channel_id=1, replica_groups=[4,2]<=[8]
  %ag = bf16[64,512]{1,0} all-gather(%p0), channel_id=2, dimensions={0}
  %rs = f32[16,256]{1,0} reduce-scatter(%ar), channel_id=3, dimensions={0}
  %cp = f32[8]{0} collective-permute(%rs), channel_id=4
  ROOT %out = f32[128,256]{1,0} add(%ar, %ar)
}
"""


def test_shape_bytes():
    assert _shape_bytes("f32[128,256]{1,0}") == 128 * 256 * 4
    assert _shape_bytes("bf16[64]") == 128
    assert _shape_bytes("(f32[2,2], s32[4])") == 16 + 16
    assert _shape_bytes("f32[]") == 4


def test_parse_collectives_synthetic():
    st = parse_collectives(SYNTH)
    assert st.count_by_kind["all-reduce"] == 1
    assert st.bytes_by_kind["all-reduce"] == 128 * 256 * 4
    assert st.bytes_by_kind["all-gather"] == 64 * 512 * 2
    assert st.bytes_by_kind["reduce-scatter"] == 16 * 256 * 4
    assert st.bytes_by_kind["collective-permute"] == 32
    assert st.total_bytes == sum(st.bytes_by_kind.values())


def test_hbm_estimator_counts_while_trips():
    def f(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, None, length=6)
        return y

    co = jax.jit(f).lower(
        jax.ShapeDtypeStruct((256, 256), jnp.float32),
        jax.ShapeDtypeStruct((256, 256), jnp.float32)).compile()
    est = estimate_hbm_bytes(co.as_text())
    # 6 trips × (read x, read w, write y) ≈ 6 × 3 × 256KB; allow fusion slack
    one_buf = 256 * 256 * 4
    assert est["total_bytes"] >= 6 * 2 * one_buf
    assert 6 in est["trip_counts"].values()


def test_roofline_terms_math():
    t = RooflineTerms(chips=256, hlo_flops_per_device=197e12,
                      hlo_bytes_per_device=819e9,
                      collective_bytes_per_device=50e9,
                      peaks=peaks_for("TPU v5 lite"),
                      model_flops=197e12 * 256)
    assert t.compute_s == pytest.approx(1.0)
    assert t.memory_s == pytest.approx(1.0)
    assert t.collective_s == pytest.approx(1.0)
    assert t.model_flops_ratio == pytest.approx(1.0)
    assert t.roofline_fraction == pytest.approx(1.0)


def test_model_flops_moe_uses_active():
    cfg = get_config("qwen3-moe-30b-a3b")
    train = model_flops_for(cfg, SHAPES["train_4k"])
    expect = 6.0 * cfg.active_params_count() * 256 * 4096
    assert train == pytest.approx(expect)
    dec = model_flops_for(cfg, SHAPES["decode_32k"])
    assert dec == pytest.approx(2.0 * cfg.active_params_count() * 128)


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", ""])
def test_peaks_unknown_device_kind_raises(kind):
    with pytest.raises(KeyError, match="no published peaks"):
        peaks_for(kind)
