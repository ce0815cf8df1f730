"""A configuration, a traffic mix, a consumer and a metric are added as new
files only: a copy of the benchmark given four new files and a new entry
in ``BENCHMARK.json`` runs the new cell, with no file of the copy edited."""
import json
import shutil

from chip_bench_cells import BENCH, ROOT, run
from chipbench import registry

CONSUMER = '''
from chipbench.runner import Check


class Consumer:
    """Counts the bytes of each batch on the host."""

    def __init__(self, cell, paths, files, tokens, seed, devices):
        self.batch = int(cell.traffic["batch"])
        self.bytes = 0

    def decode(self, blobs):
        return sum(len(b) for b in blobs)

    def setup(self, plane, spans):
        self.step(plane.next(spans))

    def step(self, batch):
        self.bytes += batch
        return self.batch

    def check(self):
        return {"bytes_read": Check(0 if self.bytes > 0 else 1, 0)}, 1, 0
'''

METRIC = '''
def read(run):
    return float(len(run.step_ends))
'''


def test_new_cell_is_found_from_new_files_only(tmp_path):
    bench = tmp_path / "benchmarks" / "chip"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        ".jax_cache", "__pycache__", "tests"))
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())

    config = json.loads((bench / "configs" / "imagenet-1k-files.json")
                        .read_text())
    config["name"] = "tiny-files"
    config["dataset"].update(num_files=64, num_classes=4, mean_bytes=1000)
    (bench / "configs" / "tiny-files.json").write_text(json.dumps(config))
    (bench / "traffic" / "count.json").write_text(json.dumps(
        {"consumer": "count_bytes", "sampler": "global_uniform",
         "read": "demand", "batch": 8, "loader_depth": 2}))
    (bench / "consumers" / "count_bytes.py").write_text(CONSUMER)
    (bench / "metrics" / "steps_done.py").write_text(METRIC)
    spec["configs"].append({"name": "tiny-files", "source": "test",
                            "file": "benchmarks/chip/configs/tiny-files.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tiny-files.count",
                              "config": "tiny-files", "traffic": "count",
                              "chips": 1, "why": "test"})
    spec["end_to_end"].append({"name": "steps_done", "unit": "steps",
                               "better": "higher", "bound": 0.01,
                               "source": "host_clock",
                               "workloads": ["tiny-files.count"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = registry.load_cell("tiny-files.count", tmp_path, bench)
    assert [m["name"] for m in cell.metrics(False)] == [
        "samples_per_s", "setup_s", "steps_done"]
    res = run(cell)
    assert res["correct"]
    assert res["metrics"]["steps_done"]["value"] >= 1
    assert res["metrics"]["samples_per_s"]["value"] > 0
    for path, data in before.items():
        assert path.read_bytes() == data, f"{path} was edited"


def test_unknown_workload_names_the_known_ones():
    try:
        registry.load_cell("no-such.cell", ROOT)
    except KeyError as e:
        assert "imagenet-1k-files.demand" in str(e)
    else:
        raise AssertionError("unknown workload accepted")
