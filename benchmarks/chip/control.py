"""Reads the controls and faults that the checks of a cell must catch.

    python3 benchmarks/chip/control.py --workload <name> --seeds 1,2,3 \
        [--seconds 5]

For a training cell (consumer ``train_step``), per seed: the control,
the plain float32 reference put in the program's place with every matrix
product at float8 (one precision below the configuration's bf16), judged
by the cell's own numbers and limits against the reference at float32;
then the cell runs for ``--seconds`` with each fault of
``TRAINING_FAULTS`` planted under its timed path. A step that returns its
state unchanged reads 1 on ``change_gap`` by construction and needs no
run.

For a data-plane cell, per seed, the cell runs for ``--seconds`` with each
fault of ``DATA_PLANE_FAULTS`` planted under its timed path.

Every line printed says whether the cell's checks came out ``correct``,
with each number beside its limit.

Runs on the chip it is started on, like ``run_cell.py``; the benchmark's
own runs never run this.
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
DATA_PLANE_FAULTS = ("answer_altered", "half_batch")
TRAINING_FAULTS = ("answer_altered", "half_batch_step")


def training_control(cell, seed: int) -> dict:
    """The float8 control's checks, by the cell's ``compare``."""
    from chipbench import gen
    mod = cell.consumer()
    ref = cell.reference()
    mc, opt, ds = cell.config["model"], cell.config["optimizer"], \
        cell.config["dataset"]
    _, _, tokens = gen.make_dataset(ds, seed, vocab=mc["vocab_size"])
    b, n = int(cell.traffic["batch"]), tokens.shape[0]
    batches = [tokens[gen.batch_indices(n, b, seed, k)]
               for k in range(int(cell.traffic["checked_steps"]))]
    t = time.perf_counter()
    base = ref.train(mc, opt, seed, batches)
    reference_s = time.perf_counter() - t
    losses, grad, change = ref.train(mc, opt, seed, batches, matmul="fp8")
    checks = mod.compare(
        {"loss": losses, "grad": mod.flat_norms(grad),
         "change": mod.flat_norms(change)},
        (base[0], mod.flat_norms(base[1]), mod.flat_norms(base[2])),
        cell.config["limits"])
    return {"correct": all(c.ok for c in checks.values()),
            "checks": {k: {"value": c.value, "limit": c.limit}
                       for k, c in checks.items()},
            "reference_s": reference_s}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)

    # the TPU runtime logs under /tmp unless told otherwise; a run writes
    # only inside its checkout and the temporary directory it is given
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"control: JAX found no TPU (platform "
                 f"{devices[0].platform!r})")
    sys.path.insert(0, str(HERE))
    from chipbench import faults, registry, runner
    root = registry.repo_root()
    sys.path.insert(0, str(root / "src"))
    cell = registry.load_cell(args.workload, root)
    training = cell.traffic["consumer"] == "train_step"
    for seed in (int(s) for s in args.seeds.split(",")):
        if training:
            print(json.dumps({"seed": seed, "control": "fp8",
                              **training_control(cell, seed)}), flush=True)
        for name in TRAINING_FAULTS if training else DATA_PLANE_FAULTS:
            res = runner.run(cell, seed, args.seconds, False,
                             time.perf_counter(), devices=devices[:cell.chips],
                             fault=faults.FAULTS[name])
            print(json.dumps({"seed": seed, "fault": name,
                              "correct": res["correct"],
                              "checks": res["checks"]}), flush=True)

if __name__ == "__main__":
    main()
