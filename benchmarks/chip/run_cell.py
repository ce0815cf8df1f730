"""Runs one cell of the chip benchmark once, on the chip it is started on.

    python3 benchmarks/chip/run_cell.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

The last line of standard output is the result, one JSON object; the
numbers compared to decide ``correct`` end standard error, each beside
its limit. Without a TPU, or with fewer chips than the cell asks for,
the run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent

# glibc's mallopt parameters
M_TRIM_THRESHOLD, M_MMAP_THRESHOLD = -1, -3


def steady_allocator() -> None:
    """Has glibc's malloc keep the memory the process frees for its next
    allocation, instead of handing it back to the kernel and faulting it
    in again: a batch's files are allocated and freed at every step, and
    with the defaults a run slowed down for seconds at a time as the
    heap was trimmed and grown again. Allocations up to 32 MiB come from
    the heap; the top of the heap is never trimmed below 2 GiB."""
    import ctypes
    try:
        mallopt = ctypes.CDLL(None).mallopt
    except AttributeError:          # not glibc
        return
    mallopt(M_TRIM_THRESHOLD, 2 ** 31 - 1)
    mallopt(M_MMAP_THRESHOLD, 32 << 20)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)
    steady_allocator()

    # the TPU runtime logs under /tmp unless told otherwise; a run writes
    # only inside its checkout and the temporary directory it is given
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"run_cell: JAX found no TPU (platform "
                 f"{devices[0].platform!r})")
    sys.path.insert(0, str(HERE))
    from chipbench import registry, runner
    root = registry.repo_root()
    sys.path.insert(0, str(root / "src"))
    cell = registry.load_cell(args.workload, root)
    if len(devices) < cell.chips:
        sys.exit(f"run_cell: {args.workload} needs {cell.chips} chips, JAX "
                 f"found {len(devices)}")
    runner.log(f"device: platform={devices[0].platform} "
               f"kind={devices[0].device_kind} count={len(devices)}")
    result = runner.run(cell, args.seed, args.seconds, bool(args.trace), T0,
                        devices=devices[:cell.chips])
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
