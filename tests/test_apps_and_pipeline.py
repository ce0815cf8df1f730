"""Paper applications (ResNet/SRGAN/FRNN minis) + pipeline parallelism."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.models.apps import FRNNMini, ResNetMini, SRGANMini

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _sgd(params, grads, lr):
    return jax.tree.map(lambda p, g: p - lr * g, params, grads)


def test_resnet_mini_trains(rng):
    model = ResNetMini(num_classes=4, width=8, n_blocks=2)
    params = model.init(jax.random.key(0))
    x = jnp.asarray(rng.standard_normal((8, 16, 16, 3)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 4, 8), jnp.int32)
    batch = {"image": x, "label": y}
    loss_g = jax.jit(jax.value_and_grad(model.loss))
    l0, g = loss_g(params, batch)
    for _ in range(10):
        l, g = loss_g(params, batch)
        params = _sgd(params, g, 0.1)
    assert np.isfinite(float(l)) and float(l) < float(l0)


def test_srgan_mini_two_stages(rng):
    model = SRGANMini(width=8, n_blocks=1)
    params = model.init(jax.random.key(0))
    lr_img = jnp.asarray(rng.standard_normal((2, 8, 8, 3)) * 0.1, jnp.float32)
    hr_img = jnp.asarray(rng.standard_normal((2, 32, 32, 3)) * 0.1, jnp.float32)
    batch = {"lr": lr_img, "hr": hr_img}
    sr = model.generate(params["gen"], lr_img)
    assert sr.shape == (2, 32, 32, 3)                 # 4x upscale
    # stage 1: pixel loss decreases
    lg = jax.jit(jax.value_and_grad(model.init_stage_loss))
    l0, g = lg(params, batch)
    for _ in range(8):
        l, g = lg(params, batch)
        params = _sgd(params, g, 0.05)
    assert float(l) < float(l0)
    # stage 2: both losses finite and g updates don't explode
    gl, dl = model.train_stage_losses(params, batch)
    assert np.isfinite(float(gl)) and np.isfinite(float(dl))


def test_frnn_mini_learns_disruptions(rng):
    model = FRNNMini(n_signals=6, hidden=16, layers=2)
    params = model.init(jax.random.key(1))
    # disrupted shots have a growing oscillation in one channel
    t = np.linspace(0, 1, 24)
    clean = rng.standard_normal((8, 24, 6)) * 0.1
    disrupted = clean.copy()
    disrupted[:, :, 0] += np.sin(40 * t) * t * 3
    x = jnp.asarray(np.concatenate([clean, disrupted]), jnp.float32)
    y = jnp.asarray([0] * 8 + [1] * 8, jnp.int32)
    batch = {"signals": x, "disrupted": y}
    lg = jax.jit(jax.value_and_grad(model.loss))
    l0, _ = lg(params, batch)
    for _ in range(40):
        l, g = lg(params, batch)
        params = _sgd(params, g, 0.2)
    assert float(l) < 0.9 * float(l0)
    logits = model.apply(params, x)
    acc = float(((logits > 0) == (np.asarray(y) > 0)).mean())
    assert acc >= 0.75


@pytest.mark.slow
def test_pipeline_parallel_matches_serial():
    code = """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import numpy as np, jax, jax.numpy as jnp
        from repro.dist.pipeline_par import pipeline_apply, split_stages
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4,), ("stage",))
        L, D, B = 8, 16, 8
        rng = np.random.default_rng(0)
        Ws = jnp.asarray(rng.standard_normal((L, D, D)) / np.sqrt(D))
        x = jnp.asarray(rng.standard_normal((B, D)), jnp.float32)

        def layer_group(w_group, h):      # (L/S, D, D) applied sequentially
            def body(h, w):
                return jnp.tanh(h @ w), None
            h, _ = jax.lax.scan(body, h, w_group)
            return h

        serial = layer_group(Ws, x)
        staged = split_stages({"w": Ws}, 4)
        out = pipeline_apply(lambda p, h: layer_group(p["w"], h),
                             staged, x, mesh=mesh, microbatches=4)
        np.testing.assert_allclose(np.asarray(out), np.asarray(serial),
                                   rtol=2e-5, atol=2e-5)
        print("OK")
    """
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=480)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout
