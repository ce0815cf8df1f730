"""Config registry + dry-run machinery (small-mesh subprocess checks)."""
import os
import subprocess
import sys
import textwrap

import pytest

from repro.configs import ARCH_IDS, SHAPES, get_config, get_smoke, \
    shape_applicable
from repro.utils.roofline import parse_collectives

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_registry_complete():
    assert len(ARCH_IDS) == 10
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        smoke = get_smoke(arch)
        assert cfg.name == arch
        assert smoke.family == cfg.family
        assert smoke.num_layers <= 4


def test_shape_applicability_matrix():
    runnable = {(a, s) for a in ARCH_IDS for s in SHAPES
                if shape_applicable(get_config(a), SHAPES[s])[0]}
    # long_500k only for ssm/hybrid
    longs = {a for (a, s) in runnable if s == "long_500k"}
    assert longs == {"falcon-mamba-7b", "hymba-1.5b"}
    # everything else runs everywhere
    assert len(runnable) == 10 * 3 + 2


def test_parse_collectives_counts_payloads():
    hlo = """
      %all-reduce.1 = f32[1024]{0} all-reduce(f32[1024]{0} %x), replica_groups={{0,1,2,3}}, to_apply=%sum
      %ag = bf16[4,2048]{1,0} all-gather(bf16[1,2048]{1,0} %y), replica_groups={{0,1,2,3}}, dimensions={0}
      %rs = f32[512]{0} reduce-scatter(f32[2048]{0} %z), replica_groups={{0,1,2,3}}, to_apply=%sum
      %cp = u8[100]{0} collective-permute(u8[100]{0} %w), source_target_pairs={{0,1}}
      %dot.5 = f32[8,8]{1,0} dot(f32[8,8]{1,0} %a, f32[8,8]{1,0} %b)
    """
    stats = parse_collectives(hlo)
    assert stats.count == 4
    assert stats.bytes_by_kind["all-reduce"] == 4096
    assert stats.bytes_by_kind["all-gather"] == 4 * 2048 * 2
    assert stats.bytes_by_kind["reduce-scatter"] == 2048 * 4
    assert stats.bytes_by_kind["collective-permute"] == 100
    # wire: ar 2x result x 3/4; ag result x 3/4; rs operand x 3/4; cp operand
    expect = 2 * 4096 * 0.75 + 16384 * 0.75 + 8192 * 0.75 + 100
    assert stats.wire_bytes == pytest.approx(expect)


@pytest.mark.slow
def test_dryrun_cell_small_mesh():
    """The dry-run machinery end-to-end on a 4x2 mesh (8 fake devices)."""
    code = """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        from repro.launch.dryrun import lower_cell, _mem_dict, _cell_costs
        from repro.launch.mesh import make_debug_mesh
        from repro.configs import get_smoke
        mesh = make_debug_mesh(4, 2)
        cfg = get_smoke("chatglm3-6b")
        # reduced shapes: monkeypatch the shape table for the subprocess
        import repro.configs.base as base
        base.SHAPES["train_4k"] = base.ShapeConfig("train_4k", 64, 8, "train")
        lowered, compiled, info = lower_cell("chatglm3-6b", "train_4k", mesh,
                                             cfg=cfg)
        mem, peak = _mem_dict(compiled)
        costs = _cell_costs(compiled)
        assert costs["flops"] > 0
        assert peak is None or peak > 0
        print("OK", int(costs["flops"]))
    """
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=480)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout


def test_mesh_factories():
    from jax.sharding import AxisType
    from repro.launch.mesh import make_debug_mesh
    m = make_debug_mesh(1, 1)
    assert m.axis_names == ("data", "model")
    assert m.axis_types == (AxisType.Auto, AxisType.Auto)


@pytest.mark.parametrize("arch,layers,want", [
    ("hymba-1.5b", 8, (0, 3, 7)),       # published (0, 15, 31) of 32
    ("hymba-1.5b", 32, (0, 15, 31)),
    ("hymba-1.5b", 1, (0,)),
    ("chatglm3-6b", 2, ()),
])
def test_with_depth_keeps_widths_and_global_layers(arch, layers, want):
    cfg = get_config(arch)
    cut = cfg.with_depth(layers)
    assert cut.num_layers == layers
    assert cut.global_layers == want
    assert cut.scaled(num_layers=cfg.num_layers,
                      global_layers=cfg.global_layers) == cfg


def test_roofline_peaks_by_device_kind():
    from repro.utils.roofline import peaks
    v5e = peaks("TPU v5 lite")
    assert (v5e.flops, v5e.hbm_bw) == (197e12, 819e9)
    with pytest.raises(ValueError, match="no published peaks"):
        peaks("cpu")


@pytest.mark.parametrize("env_dir", [None, "/elsewhere/jax-cache"])
def test_compile_cache_dir(monkeypatch, env_dir):
    import jax
    from repro.launch import compile_cache
    was = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    else:
        monkeypatch.setenv(compile_cache.ENV_VAR, env_dir)
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        got = compile_cache.enable_compile_cache()
        if env_dir is None:
            assert got == os.path.join(ROOT, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
        else:
            # JAX reads the variable itself; nothing is set in code
            assert got == env_dir
            assert jax.config.jax_compilation_cache_dir is None
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
