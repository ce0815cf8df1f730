"""Device-resident FanStore fetch: multi-device tests via subprocess.

Tests spawn a child python with XLA_FLAGS forcing 8 host devices so the main
pytest process keeps the default single-device view (dry-run contract).
"""
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_in_subprocess(code: str) -> str:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=480)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


def test_fetch_uniform_and_overflow():
    print(run_in_subprocess("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import DeviceStore, DeviceStoreConfig, tokens_from_payload
        from repro.launch.mesh import make_debug_mesh
        mesh = make_debug_mesh(4, 2)
        S, L, G = 64, 8, 16
        tokens = np.arange(S*L, dtype=np.int32).reshape(S, L)
        rng = np.random.default_rng(0)
        idx = rng.permutation(S)[:G].astype(np.int32)
        st = DeviceStore(mesh, DeviceStoreConfig(num_samples=S, sample_bytes=L*4,
                                                 capacity_factor=4.0))
        with mesh:
            arr = st.place_tokens(tokens)
            b, o = jax.jit(st.fetch)(arr, jax.device_put(idx, st.idx_sharding))
            np.testing.assert_array_equal(
                np.asarray(tokens_from_payload(b, L)), tokens[idx])
            assert not np.asarray(o).any()
        # skew at capacity_factor 2 (cap < g_local): overflow flag must trip
        st2 = DeviceStore(mesh, DeviceStoreConfig(num_samples=S, sample_bytes=L*4,
                                                  capacity_factor=2.0))
        with mesh:
            arr2 = st2.place_tokens(tokens)
            skew = np.zeros(G, dtype=np.int32)
            _, o2 = jax.jit(st2.fetch)(arr2, jax.device_put(skew, st2.idx_sharding))
            assert np.asarray(o2).any()
        print("OK")
    """))


def test_fetch_stratified_zero_waste():
    print(run_in_subprocess("""
        import numpy as np, jax
        from repro.core import DeviceStore, DeviceStoreConfig, tokens_from_payload
        from repro.data.sampler import StratifiedSampler
        from repro.launch.mesh import make_debug_mesh
        mesh = make_debug_mesh(4, 2)
        S, L, G = 128, 8, 32
        tokens = np.arange(S*L, dtype=np.int32).reshape(S, L)
        samp = StratifiedSampler(S, G, num_shards=4, seed=1)
        st = DeviceStore(mesh, DeviceStoreConfig(num_samples=S, sample_bytes=L*4,
                                                 capacity_factor=1.0))
        with mesh:
            arr = st.place_tokens(tokens)
            f = jax.jit(st.fetch)
            for _ in range(samp.steps_per_epoch):
                idx = samp.next_batch()
                b, o = f(arr, jax.device_put(idx, st.idx_sharding))
                np.testing.assert_array_equal(
                    np.asarray(tokens_from_payload(b, L)), tokens[idx])
                assert not np.asarray(o).any()
        print("OK")
    """))


def test_fetch_multi_pod_and_replication():
    print(run_in_subprocess("""
        import numpy as np, jax
        from repro.core import DeviceStore, DeviceStoreConfig, tokens_from_payload
        from repro.launch.mesh import make_debug_mesh
        mesh = make_debug_mesh(2, 2, pods=2)
        S, L, G = 64, 8, 16
        tokens = np.arange(S*L, dtype=np.int32).reshape(S, L)
        rng = np.random.default_rng(3)
        idx = rng.permutation(S)[:G].astype(np.int32)
        for pod_axis in (None, "pod"):   # replicated vs pod-sharded store
            st = DeviceStore(mesh, DeviceStoreConfig(
                num_samples=S, sample_bytes=L*4, pod_axis=pod_axis,
                capacity_factor=4.0))
            with mesh:
                arr = st.place_tokens(tokens)
                b, o = jax.jit(st.fetch)(arr, jax.device_put(idx, st.idx_sharding))
                np.testing.assert_array_equal(
                    np.asarray(tokens_from_payload(b, L)), tokens[idx])
        print("OK")
    """))


def test_fetch_dequant_pipeline():
    """Compressed store: int8 records + scales, dequant after fetch."""
    print(run_in_subprocess("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import DeviceStore, DeviceStoreConfig
        from repro.core.codec import block_quantize, block_dequantize_host
        from repro.kernels import ops
        from repro.launch.mesh import make_debug_mesh
        mesh = make_debug_mesh(4, 2)
        S, F = 32, 512
        rng = np.random.default_rng(0)
        x = rng.standard_normal((S, F)).astype(np.float32)
        q, scales = block_quantize(x)   # (S,F) int8 + (S,F//256) f16
        payload = np.concatenate(
            [q.view(np.uint8), scales.view(np.uint8),
             np.zeros((S, 4), np.uint8)], axis=1)  # packed record, pad to 8B
        st = DeviceStore(mesh, DeviceStoreConfig(
            num_samples=S, sample_bytes=payload.shape[1], capacity_factor=4.0))
        idx = rng.permutation(S)[:8].astype(np.int32)
        with mesh:
            arr = st.place(payload)
            b, _ = jax.jit(st.fetch)(arr, jax.device_put(idx, st.idx_sharding))
            b = np.asarray(jax.device_get(b))
        qf = b[:, :F].view(np.int8)
        sf = b[:, F:F + F // 256 * 2].view(np.float16)
        out = np.asarray(ops.dequant(jnp.asarray(qf), jnp.asarray(sf),
                                     impl="ref", out_dtype=jnp.float32))
        np.testing.assert_allclose(out, block_dequantize_host(q, scales)[idx],
                                   rtol=1e-3, atol=1e-3)
        print("OK")
    """))


def test_int8_grad_sync_matches_fp32():
    print(run_in_subprocess("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.configs import get_smoke
        from repro.models import build_model
        from repro.train.optimizer import OptimizerConfig
        from repro.train.train_step import make_train_step, init_state
        from repro.launch.mesh import make_debug_mesh
        mesh = make_debug_mesh(4, 2)
        cfg = get_smoke("chatglm3-6b").scaled(remat=False)
        model = build_model(cfg)
        ocfg = OptimizerConfig(lr=5e-3, warmup_steps=1, total_steps=40)
        rng = np.random.default_rng(0)
        batch = {"tokens": jnp.asarray(
            rng.integers(0, cfg.vocab_size, (16, 32)).astype(np.int32))}
        with mesh:
            sa = init_state(model, jax.random.key(0), ocfg)
            step_a = jax.jit(make_train_step(model, ocfg))
            si = init_state(model, jax.random.key(0), ocfg, grad_sync="int8")
            step_i = jax.jit(make_train_step(model, ocfg, mesh=mesh,
                                             dp_axes=("data",),
                                             grad_sync="int8"))
            for _ in range(6):
                sa, ma = step_a(sa, batch)
                si, mi = step_i(si, batch)
        la, li = float(ma["loss"]), float(mi["loss"])
        assert li < 4.6 and abs(la - li) < 0.2, (la, li)
        print("OK", la, li)
    """))
