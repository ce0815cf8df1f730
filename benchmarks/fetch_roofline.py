"""§Perf hillclimb cell 3: the FanStore fetch step itself, on the
production 16x16 mesh (256 chips) — the cell most representative of the
paper's technique.

Workload: train_4k's data need — G=256 samples/step of 16 KiB records
(4k tokens x int32) from a 2 TiB-class store (samples scaled so the HBM
slice stays in placeholder range; wire bytes scale exactly with G x bytes).

Arms (hypothesis -> expected collective-term delta):
  A. uniform cf=2.0 (paper-faithful: random access + capacity headroom)
  B. stratified cf=1.0 (beyond-paper: balanced sampler -> zero padding,
     expected ~2x wire reduction vs A)
  C. stratified + int8 block-quantized payload + scales (wire ~/2 again;
     dequant runs at HBM bw on device — the paper's Fig-10 trade on ICI)

A CPU compile, not a chip run: the child runs with ``JAX_PLATFORMS=cpu``
and 512 fake host devices, parses the compiled HLO's collective payloads
(same methodology as the dry-run roofline), and turns wire bytes into a
collective term with the published TPU v5e link rate. A failed child
raises.
"""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap
from typing import List

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CODE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core import DeviceStore, DeviceStoreConfig
from repro.launch.mesh import make_production_mesh
from repro.utils.roofline import parse_collectives, peaks

assert jax.devices()[0].platform == "cpu"
LINK_BW = peaks("TPU v5 lite").link_bw
mesh = make_production_mesh(multi_pod=False)
G = 256
SEQ = 4096
S = 256 * 64                       # samples (64 per data shard)

def lower_arm(name, sample_bytes, cf):
    cfgs = DeviceStoreConfig(num_samples=S, sample_bytes=sample_bytes,
                             capacity_factor=cf)
    st = DeviceStore(mesh, cfgs)
    store_sds = jax.ShapeDtypeStruct((S, sample_bytes), jnp.uint8,
                                     sharding=st.store_sharding)
    idx_sds = st.idx_spec(G)
    with mesh:
        lowered = jax.jit(st.fetch).lower(store_sds, idx_sds)
        compiled = lowered.compile()
    stats = parse_collectives(compiled.as_text())
    term_us = stats.wire_bytes / LINK_BW * 1e6
    print(f"fetch_arm,{name},cpu_compile,target=TPU v5 lite,"
          f"cf={cf},sample_bytes={sample_bytes},"
          f"wire_bytes={int(stats.wire_bytes)},coll_term_us={term_us:.1f},"
          f"by_kind={stats.bytes_by_kind}")
    return stats.wire_bytes

raw = SEQ * 4                       # int32 tokens
quant = SEQ + SEQ // 256 * 2        # int8 payload + f16 scales (4x smaller)
quant = -(-quant // 64) * 64        # pad to the byte-sharding granule
a = lower_arm("A_uniform_bf16", raw, 2.0)
b = lower_arm("B_stratified", raw, 1.0)
c = lower_arm("C_strat_int8", quant, 1.0)
print(f"fetch_arm,summary,B_vs_A={a/b:.2f}x,C_vs_A={a/c:.2f}x")
"""


def main() -> List[str]:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(_CODE)],
                         capture_output=True, text=True, env=env,
                         timeout=580)
    if out.returncode != 0:
        raise RuntimeError(f"fetch_roofline child failed "
                           f"(rc={out.returncode}):\n"
                           f"{out.stderr.strip()[-2000:]}")
    return [l for l in out.stdout.splitlines() if l.startswith("fetch_arm,")]


if __name__ == "__main__":
    for line in main():
        print(line)
