"""Device-tier FanStore exchange, compiled and run on 8 fake CPU devices.

Lowers the ``core/fetch.py`` all_to_all on a (4, 2) ("data", "model") mesh
of host devices and counts its collective payload per step, for uniform
(capacity 2.0) against stratified (capacity 1.0) sampling: the stratified
sampler halves the all_to_all payload. The byte counts carry over to a
chip; ``cpu_wall_us`` is the XLA CPU backend's time and says nothing about
a TPU.

The child runs with ``JAX_PLATFORMS=cpu`` so it never opens an
accelerator, and a failed child raises instead of returning a line.
"""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap
from typing import List

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CODE = """
import numpy as np, jax, jax.numpy as jnp, time
from repro.core import DeviceStore, DeviceStoreConfig
from repro.data.sampler import StratifiedSampler
from repro.launch.mesh import make_mesh
from repro.utils.roofline import parse_collectives

assert jax.devices()[0].platform == "cpu"
mesh = make_mesh((4, 2), ("data", "model"))
S, B = 4096, 4096             # samples x bytes
G = 256
rng = np.random.default_rng(0)
records = rng.integers(0, 255, (S, B), dtype=np.uint8)

for name, cf in (("uniform", 2.0), ("stratified", 1.0)):
    st = DeviceStore(mesh, DeviceStoreConfig(num_samples=S, sample_bytes=B,
                                             capacity_factor=cf))
    with mesh:
        arr = st.place(records)
        if name == "uniform":
            idx = rng.permutation(S)[:G].astype(np.int32)
        else:
            idx = StratifiedSampler(S, G, num_shards=4).next_batch()
        idxd = jax.device_put(idx, st.idx_sharding)
        fetched = jax.jit(st.fetch)
        lowered = fetched.lower(arr, idxd)
        compiled = lowered.compile()
        stats = parse_collectives(compiled.as_text())
        t0 = time.perf_counter()
        for _ in range(5):
            out, ovf = fetched(arr, idxd)
        jax.block_until_ready(out)
        dt = (time.perf_counter() - t0) / 5
        print(f"fetch,{name},platform=cpu,devices={len(jax.devices())},"
              f"cf={cf},wire_bytes={int(stats.wire_bytes)},"
              f"coll_ops={stats.count},cpu_wall_us={dt*1e6:.0f},"
              f"payload_bytes={G*B}")
"""


def main() -> List[str]:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(_CODE)],
                         capture_output=True, text=True, env=env, timeout=480)
    if out.returncode != 0:
        raise RuntimeError(f"fetch child failed (rc={out.returncode}):\n"
                           f"{out.stderr.strip()[-2000:]}")
    return [l for l in out.stdout.splitlines() if l.startswith("fetch,")]


if __name__ == "__main__":
    for line in main():
        print(line)
