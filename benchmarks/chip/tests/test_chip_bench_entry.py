"""The entry point's refusals, the peaks table and the FLOP count."""
import json
import math
import os
import subprocess
import sys

import jax
import pytest

from chip_bench_cells import BENCH, ROOT
from chipbench import flops, peaks


def test_run_cell_without_a_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, str(BENCH / "run_cell.py"), "--workload",
         "imagenet-1k-files.demand", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=120)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    for line in out.stdout.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)


def test_unknown_device_kind_has_no_peaks():
    assert peaks.peaks("TPU v5 lite").bf16_flops == 197e12
    with pytest.raises(ValueError):
        peaks.peaks("cpu")


def test_hymba_8l_flops_per_step_follow_the_configuration():
    from repro.configs.base import ModelConfig
    from repro.models import build_model
    mc = json.loads((BENCH / "configs" / "hymba-1.5b-8l.json")
                    .read_text())["model"]
    seq = 4096
    cfg = ModelConfig(**{**mc, "global_layers": tuple(mc["global_layers"])})
    shapes = jax.eval_shape(build_model(cfg).init, jax.random.key(0))
    got = 4 * flops.train_flops_per_sample(flops.param_leaves(shapes), seq)

    d, v, h, kv, dh, f = (mc[k] for k in ("d_model", "vocab_size",
                                          "num_heads", "num_kv_heads",
                                          "head_dim", "d_ff"))
    di, st, dtr, k = 2 * d, mc["ssm_state"], mc["dt_rank"], mc["ssm_conv"]
    attn = d * h * dh * 2 + d * kv * dh * 2
    mamba = (d * 2 * di + k * di + di + di * (dtr + 2 * st) + dtr * di
             + di + di * st + di + di * d)
    layer = attn + mamba + 3 * d * f + 4 * d      # four norm scales
    n = mc["num_layers"] * layer + v * d + d      # output table, final norm
    assert got == 6 * n * seq * 4
    # about 10.8 TFLOP per sample, 43 TFLOP per step of 4 x 4,096 tokens
    assert math.isclose(got, 4.34e13, rel_tol=0.01)
