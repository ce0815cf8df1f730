"""The Pallas kernels compile for a TPU v5e chip, at real widths.

Nothing runs: each test lowers a kernel through its ``ops`` wrapper for a
described (not attached) v5e chip and compiles it with the TPU compiler,
which refuses what interpret mode accepts (misaligned blocks, lowerings
the TPU backend lacks, too much VMEM). The topology is described inside a
fixture, never at import, so that only the test worker given this file
loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip; keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_hlo(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def test_dequant_compiles_for_v5e(one_chip):
    n, f = 1024, 4096
    hlo = _compiled_hlo(lambda q, s: ops.dequant(q, s, impl="kernel"),
                        one_chip, ((n, f), jnp.int8),
                        ((n, f // 256), jnp.float16))
    assert "tpu_custom_call" in hlo


def test_ssm_scan_compiles_for_v5e(one_chip):
    cfg = get_config("falcon-mamba-7b")
    d, s, t = cfg.d_inner, cfg.ssm_state, 2048
    assert (d, s) == (8192, 16)
    hlo = _compiled_hlo(
        lambda *a: ops.ssm_scan(*a, impl="kernel"), one_chip,
        ((1, t, d), jnp.bfloat16), ((1, t, d), jnp.bfloat16),
        ((1, t, s), jnp.bfloat16), ((1, t, s), jnp.bfloat16),
        ((d, s), jnp.float32), ((d,), jnp.float32))
    assert "tpu_custom_call" in hlo


def test_flash_attn_compiles_for_v5e(one_chip):
    cfg = get_config("chatglm3-6b")
    h, kv, dh, t = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, 4096
    assert (h, kv, dh) == (32, 2, 128)
    hlo = _compiled_hlo(
        lambda q, k, v: ops.attention(q, k, v, impl="kernel"), one_chip,
        ((1, t, h, dh), jnp.bfloat16), ((1, t, kv, dh), jnp.bfloat16),
        ((1, t, kv, dh), jnp.bfloat16))
    assert "tpu_custom_call" in hlo
