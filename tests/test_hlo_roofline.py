"""HLO collective parser, roofline arithmetic, peaks and compile cache."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.utils.hlo import (collective_bytes, parse_hlo_collectives,
                             _shape_bytes)
from repro.utils.roofline import (HW, V5E_KIND, RooflineTerms, peaks,
                                  roofline_from_analysis)


def test_shape_bytes():
    assert _shape_bytes("bf16[4,128]{1,0}") == 4 * 128 * 2
    assert _shape_bytes("f32[16]") == 64
    assert _shape_bytes("(f32[2]{0}, bf16[3,3]{1,0})") == 8 + 18
    assert _shape_bytes("pred[7]") == 7


SAMPLE_HLO = """
HloModule jit_f

%region_0.10 (a: f32[4]) -> f32[4] {
  ROOT %add = f32[4]{0} add(...)
}

%while_body.3 (arg: (s32[], bf16[8,16])) -> (s32[], bf16[8,16]) {
  %ag = bf16[8,16]{1,0} all-gather(bf16[8,4]{1,0} %x), dimensions={1}
  ROOT %t = (s32[], bf16[8,16]) tuple(...)
}

ENTRY %main () -> f32[2] {
  %ar = f32[64,32]{1,0} all-reduce(f32[64,32]{1,0} %p), to_apply=%region_0.10
  %rs = f32[8,32]{1,0} reduce-scatter(f32[64,32]{1,0} %q), dimensions={0}
  %cp = bf16[128]{0} collective-permute(bf16[128]{0} %r)
  %a2a = f32[4,4]{1,0} all-to-all(f32[4,4]{1,0} %s)
}
"""


def test_parse_collectives_kinds_and_sizes():
    got = parse_hlo_collectives(SAMPLE_HLO)
    kinds = sorted(k for _, k, _ in got)
    assert kinds == sorted(["all-gather", "all-reduce", "reduce-scatter",
                            "collective-permute", "all-to-all"])
    sizes = {k: b for _, k, b in got}
    assert sizes["all-reduce"] == 64 * 32 * 4
    assert sizes["all-gather"] == 8 * 16 * 2
    assert sizes["reduce-scatter"] == 8 * 32 * 4


def test_body_multipliers_scale_loop_collectives():
    base = collective_bytes(SAMPLE_HLO)
    scaled = collective_bytes(SAMPLE_HLO, body_multipliers={"while": 10})
    assert scaled["all-gather"] == 10 * base["all-gather"]
    assert scaled["all-reduce"] == base["all-reduce"]


def test_parser_on_real_compiled_module():
    """End-to-end on an actually compiled SPMD module (1-device fallback:
    no collectives is acceptable; on sharded builds they appear)."""
    f = jax.jit(lambda x: jnp.sum(x * x))
    txt = f.lower(jax.ShapeDtypeStruct((128, 128), jnp.float32)) \
           .compile().as_text()
    got = collective_bytes(txt)
    assert got["total"] >= 0


def test_roofline_terms_and_bottleneck():
    hw = HW(peak_flops=100.0, hbm_bw=10.0, ici_bw=1.0)
    t = RooflineTerms(flops_per_device=1000.0, hbm_bytes_per_device=50.0,
                      collective_bytes_per_device=2.0,
                      model_flops_global=8000.0, chips=16, hw=hw)
    assert t.t_compute == pytest.approx(10.0)
    assert t.t_memory == pytest.approx(5.0)
    assert t.t_collective == pytest.approx(2.0)
    assert t.bottleneck == "compute"
    assert t.step_time_lower_bound == pytest.approx(10.0)
    assert t.useful_flops_fraction == pytest.approx(8000.0 / 16000.0)
    # mfu at the bound: model flops / (chips * peak * t)
    assert t.mfu_bound == pytest.approx(8000.0 / (16 * 100.0 * 10.0))


def test_roofline_from_cost_analysis_dict():
    t = roofline_from_analysis({"flops": 10.0, "bytes accessed": 20.0},
                               collective_bytes_per_device=5.0,
                               model_flops_global=100.0, chips=4,
                               hw=peaks(V5E_KIND))
    assert t.flops_per_device == 10.0
    assert t.hbm_bytes_per_device == 20.0
    assert t.collective_bytes_per_device == 5.0


def test_roofline_normalizes_cost_analysis_jax_flavors():
    """``compiled.cost_analysis()`` gives a dict, or None where the backend
    has no estimate; both must work."""
    d = {"flops": 10.0, "bytes accessed": 20.0}
    hw = peaks(V5E_KIND)
    t = roofline_from_analysis(d, collective_bytes_per_device=5.0,
                               model_flops_global=100.0, chips=4, hw=hw)
    assert t.flops_per_device == 10.0
    assert t.hbm_bytes_per_device == 20.0
    t = roofline_from_analysis(None, collective_bytes_per_device=5.0,
                               model_flops_global=100.0, chips=4, hw=hw)
    assert t.flops_per_device == 0.0
    f = jax.jit(lambda x: x @ x).lower(
        jax.ShapeDtypeStruct((128, 128), jnp.float32)).compile()
    assert roofline_from_analysis(f.cost_analysis(), 0.0, 1.0, 1,
                                  hw).flops_per_device > 0


def test_peaks_are_keyed_by_device_kind():
    assert peaks("TPU v5 lite").peak_flops == 197e12
    with pytest.raises(ValueError, match="no published peaks"):
        peaks("cpu")


def test_compile_cache_honours_env_else_repo_path(monkeypatch, tmp_path):
    import os

    from repro.utils.compile_cache import CACHE_DIR, enable_compile_cache
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert CACHE_DIR == os.path.join(repo, ".jax_cache")
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == prev   # JAX's own
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert enable_compile_cache() == CACHE_DIR
        assert jax.config.jax_compilation_cache_dir == CACHE_DIR
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
