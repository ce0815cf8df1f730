"""Smoke run of FanStore's training path and device tier on a TPU.

  python3 chip_smoke.py             # one chip
  python3 chip_smoke.py --chips 4   # four chips of one host

One chip runs two phases.

- trainer: hymba-1.5b at its published widths, cut to 8 layers, trains 10
  steps through ``repro.launch.train.run``. Token records sit in FanStore
  partitions on 4 modeled nodes; ``cluster.connect`` sessions read them
  through the ``PrefetchLoader`` into the jitted train step. The first two
  batches the loader hands to the step must be byte-identical to the host
  reference (``token_dataset`` rows in the sampler's order), and every
  step's loss must be finite.
- device tier: the same records placed in HBM by ``DeviceStore`` on a 1x1
  mesh; a 256-index fetch compared byte for byte with ``records[idx]``.
  Then the compiled dequant kernel against the NumPy codec, within half a
  quantization step.

Four chips run only what exists across chips: the ``core/fetch.py``
all_to_all on a (4, 1) ("data", "model") mesh, uniform at capacity 2.0 and
stratified at 1.0, byte for byte; and one data-parallel train step, whose
loss is compared with the same step on one chip.

Without a TPU the script exits non-zero and prints no result. A passing
run ends with one JSON line, ``{"ok": true, "device": {...}}``; the lines
before it (compile time, step time, peak HBM) are for reading, not metrics.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "hymba-1.5b"
LAYERS = 8
SEQ_LEN = 4096            # one 16 KiB record of int32 tokens per sample
NUM_SAMPLES = 8192        # 128 MiB of records
GLOBAL_BATCH = 4
STEPS = 10
WARMUP_STEPS = 2
NODES = 4
SEED = 0
FETCH_BATCH = 256
DEQUANT_SHAPE = (1024, 4096)
# Data-parallel loss against one chip: the two programs reduce in another
# order; two bf16 ulps of the loss bound that.
LOSS_RTOL = 2.0 ** -7


def log(msg: str) -> None:
    print(msg, flush=True)


def train_argv() -> list:
    return ["--arch", ARCH, "--preset", "full", "--layers", str(LAYERS),
            "--seq-len", str(SEQ_LEN), "--global-batch", str(GLOBAL_BATCH),
            "--num-samples", str(NUM_SAMPLES), "--nodes", str(NODES),
            "--backend", "modeled", "--steps", str(STEPS),
            "--seed", str(SEED)]


def reference_tokens(vocab: int) -> np.ndarray:
    from repro.data.synthetic import token_dataset
    return token_dataset(NUM_SAMPLES, SEQ_LEN, vocab, seed=SEED)


def as_records(tokens: np.ndarray) -> np.ndarray:
    return np.ascontiguousarray(tokens, "<i4").view(np.uint8).reshape(
        tokens.shape[0], -1)


def check_spans(name: str, x, n: int) -> None:
    devices = {s.device for s in x.addressable_shards}
    if len(devices) != n:
        raise AssertionError(f"{name} lives on {len(devices)} devices, "
                             f"not {n}")


def peak_hbm_bytes():
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def phase_trainer(tokens: np.ndarray) -> None:
    from repro.data.sampler import GlobalUniformSampler
    from repro.launch import train

    seen = {}

    def on_batch(step, batch):
        if step < 2:
            seen[step] = np.asarray(batch["tokens"])

    out = train.run(train.parse_args(train_argv()), on_batch=on_batch)
    sampler = GlobalUniformSampler(NUM_SAMPLES, GLOBAL_BATCH, seed=SEED)
    for step in range(2):
        want = tokens[sampler.next_batch()]
        got = seen.get(step)
        if got is None or got.dtype != want.dtype or \
                got.tobytes() != want.tobytes():
            raise AssertionError(f"loader batch {step} differs from the "
                                 f"host reference")
    losses = np.asarray(out["losses"])
    if losses.shape != (STEPS,) or not np.isfinite(losses).all():
        raise AssertionError(f"losses not finite: {losses.tolist()}")
    steady = out["step_s"][WARMUP_STEPS:]
    log(f"trainer: arch={ARCH} layers={LAYERS} seq_len={SEQ_LEN} "
        f"global_batch={GLOBAL_BATCH} steps={len(losses)} "
        f"compile_s={out['compile_s']} "
        f"step_s_median={float(np.median(steady))} "
        f"step_s={out['step_s']} "
        f"loss_first={losses[0]} loss_last={losses[-1]} "
        f"peak_bytes_in_use={peak_hbm_bytes()}")


def phase_device_tier(tokens: np.ndarray) -> None:
    import jax
    import jax.numpy as jnp
    from repro.core import DeviceStore, DeviceStoreConfig
    from repro.core.codec import BLOCK, block_dequantize_host, block_quantize
    from repro.kernels import ops
    from repro.launch.mesh import make_mesh

    records = as_records(tokens)
    mesh = make_mesh((1, 1), ("data", "model"))
    store = DeviceStore(mesh, DeviceStoreConfig(
        num_samples=NUM_SAMPLES, sample_bytes=records.shape[1],
        capacity_factor=1.0))
    rng = np.random.default_rng(SEED)
    idx = rng.choice(NUM_SAMPLES, FETCH_BATCH, replace=False).astype(np.int32)
    arr = store.place(records)
    out, overflow = jax.jit(store.fetch)(
        arr, jax.device_put(idx, store.idx_sharding))
    if np.asarray(overflow).any() or \
            not np.array_equal(np.asarray(out), records[idx]):
        raise AssertionError("DeviceStore fetch differs from records[idx]")
    del arr, out
    log(f"device_store: records={NUM_SAMPLES}x{records.shape[1]}B "
        f"fetch={FETCH_BATCH} byte-exact")

    x = rng.standard_normal(DEQUANT_SHAPE).astype(np.float32)
    q, scales = block_quantize(x)
    deq = jax.jit(lambda q, s: ops.dequant(q, s, impl="kernel",
                                           out_dtype=jnp.float32))
    t0 = time.perf_counter()
    compiled = deq.lower(q, scales).compile()
    compile_s = time.perf_counter() - t0
    if "tpu_custom_call" not in compiled.as_text():
        raise AssertionError("dequant did not compile to the Pallas kernel")
    y = np.asarray(compiled(jnp.asarray(q), jnp.asarray(scales)))
    half = np.repeat(scales.astype(np.float32), BLOCK, axis=1) / 2
    if not (np.abs(y - block_dequantize_host(q, scales)) <= half).all():
        raise AssertionError("dequant kernel differs from the host codec")
    log(f"dequant: shape={DEQUANT_SHAPE} kernel=tpu_custom_call "
        f"compile_s={compile_s} within scale/2")


def phase_exchange(mesh, tokens: np.ndarray) -> None:
    import jax
    from repro.core import DeviceStore, DeviceStoreConfig
    from repro.data.sampler import GlobalUniformSampler, StratifiedSampler

    records = as_records(tokens)
    shards = mesh.shape["data"]
    arms = (("uniform", 2.0,
             GlobalUniformSampler(NUM_SAMPLES, FETCH_BATCH, seed=SEED)),
            ("stratified", 1.0,
             StratifiedSampler(NUM_SAMPLES, FETCH_BATCH, num_shards=shards,
                               seed=SEED)))
    for name, cf, sampler in arms:
        store = DeviceStore(mesh, DeviceStoreConfig(
            num_samples=NUM_SAMPLES, sample_bytes=records.shape[1],
            capacity_factor=cf))
        arr = store.place(records)
        idx = sampler.next_batch()
        out, overflow = jax.jit(store.fetch)(
            arr, jax.device_put(idx, store.idx_sharding))
        check_spans(f"{name} store", arr, mesh.size)
        check_spans(f"{name} batch", out, mesh.size)
        overflow = np.asarray(overflow)
        if name == "stratified" and overflow.any():
            raise AssertionError("stratified exchange overflowed")
        if not np.array_equal(np.asarray(out), records[idx]):
            raise AssertionError(f"{name} exchange differs from "
                                 f"records[idx]")
        del arr, out
        log(f"exchange: {name} cf={cf} mesh=({shards},1) "
            f"fetch={FETCH_BATCH} overflow={overflow.tolist()} byte-exact")


def phase_data_parallel(mesh, tokens: np.ndarray) -> None:
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import get_config
    from repro.data.sampler import GlobalUniformSampler
    from repro.models import build_model
    from repro.train.optimizer import OptimizerConfig
    from repro.train.train_step import init_state, make_train_step

    model = build_model(get_config(ARCH).with_depth(LAYERS))
    ocfg = OptimizerConfig(lr=1e-3, warmup_steps=WARMUP_STEPS,
                           total_steps=STEPS)
    # one host copy feeds both programs, so they start from the same bytes
    state = jax.device_get(jax.jit(lambda k: init_state(model, k, ocfg))(
        jax.random.key(SEED)))
    idx = GlobalUniformSampler(NUM_SAMPLES, GLOBAL_BATCH,
                               seed=SEED).next_batch()
    batch = {"tokens": tokens[idx]}
    step = make_train_step(model, ocfg, grad_sync="auto")

    one = jax.devices()[0]
    new1, m1 = jax.jit(step, donate_argnums=0)(jax.device_put(state, one),
                                               jax.device_put(batch, one))
    loss1 = float(m1["loss"])
    del new1, m1          # device 0 holds a replica of the state next

    rep = NamedSharding(mesh, P())
    state4 = jax.device_put(state, rep)
    batch4 = jax.device_put(batch, NamedSharding(mesh, P("data")))
    check_spans("dp batch", batch4["tokens"], mesh.size)
    check_spans("dp params", jax.tree.leaves(state4.params)[0], mesh.size)
    new4, m4 = jax.jit(step, donate_argnums=0)(state4, batch4)
    loss4 = float(m4["loss"])
    check_spans("dp new params", jax.tree.leaves(new4.params)[0], mesh.size)
    if not (np.isfinite(loss4)
            and abs(loss4 - loss1) <= LOSS_RTOL * abs(loss1)):
        raise AssertionError(f"data-parallel loss {loss4} vs one-chip "
                             f"{loss1}")
    log(f"data_parallel: mesh=({mesh.size},1) global_batch={GLOBAL_BATCH} "
        f"loss_4chip={loss4} loss_1chip={loss1} "
        f"abs_diff={abs(loss4 - loss1)} rtol={LOSS_RTOL}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4])
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform "
                 f"{devices[0].platform!r})")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} "
                 f"devices, JAX found {len(devices)}")

    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_mesh

    log(f"device: platform={devices[0].platform} "
        f"kind={devices[0].device_kind} count={len(devices)} "
        f"compile_cache={enable_compile_cache()}")
    t0 = time.perf_counter()
    tokens = reference_tokens(get_config(ARCH).vocab_size)
    log(f"data: tokens={tokens.shape} made in "
        f"{time.perf_counter() - t0}s")
    if args.chips == 1:
        phase_trainer(tokens)
        phase_device_tier(tokens)
    else:
        mesh = make_mesh((args.chips, 1), ("data", "model"),
                         devices[:args.chips])
        phase_exchange(mesh, tokens)
        phase_data_parallel(mesh, tokens)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
