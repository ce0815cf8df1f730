"""Cells of the chip benchmark cut to sizes a CPU test can hold.

The widths, file counts and window shrink; everything else (the data
plane, the consumers, the checks) is what a chip run uses.
"""
from __future__ import annotations

import json
import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench import registry, runner  # noqa: E402

TINY_MODEL = dict(num_layers=4, d_model=64, vocab_size=128, num_heads=4,
                  num_kv_heads=2, head_dim=16, d_ff=128, window=32,
                  global_layers=[0, 3], dt_rank=8)


def imagenet_cell():
    cell = registry.load_cell("imagenet-1k-files.demand", ROOT)
    cell.config["dataset"].update(num_files=256, num_classes=10,
                                  mean_bytes=4000)
    cell.traffic.update(batch=16, readback_batches=4)
    return cell


def hymba_cell():
    """The training cell that waits for a later benchmark (it is not in
    ``BENCHMARK.json``), with that file's metrics."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = registry.build_cell("hymba-1.5b-8l.train-4k", "hymba-1.5b-8l",
                               "train-4k", 1, spec)
    cell.config["model"].update(TINY_MODEL)
    cell.config["dataset"].update(num_files=32, seq_len=128)
    return cell


def run(cell, seed=2 ** 31 + 99, seconds=0.5, trace=False, **kw):
    """One run of ``cell`` on the CPU, with JAX's persistent cache off."""
    import jax
    jax.config.update("jax_enable_compilation_cache", False)
    return runner.run(cell, seed, seconds, trace, time.perf_counter(), **kw)
