"""Find everything a cell needs by the names in ``BENCHMARK.json``.

Nothing here knows a cell, a model or a metric: a cell names a
configuration (whose entry names its file) and a traffic mix (a file
``traffic/<mix>.json`` beside the configuration's directory); the
configuration file names its family (``families/<family>.py``), the
traffic file its driver (``drivers/<driver>.py``); a per-layer metric
is read by ``layer_metrics/<metric>.py``. So a later PR adds a cell,
a model, a mix or a metric by adding files and entries, and edits
nothing that is there.
"""
import dataclasses
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_ROOT = os.path.dirname(BENCH_DIR)


def read_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(kind, name, bench_dir=BENCH_DIR):
    """The module ``<bench_dir>/<kind>/<name>.py``, loaded by path (a
    metric's name may hold dots)."""
    path = os.path.join(bench_dir, kind, name + ".py")
    if not os.path.exists(path):
        raise FileNotFoundError(
            f"{name!r} has no file {kind}/{name}.py under {bench_dir}")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name.replace('.', '_').replace('-', '_')}",
        path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict            # the configuration file's content
    traffic: dict           # the traffic file's content
    end_to_end: list        # BENCHMARK.json metric entries of this cell
    per_layer: list
    bench_dir: str

    def family(self):
        return load_module("families", self.config["family"],
                           self.bench_dir)

    def driver(self):
        return load_module("drivers", self.traffic["driver"],
                           self.bench_dir)

    def reader(self, metric_name):
        return load_module("layer_metrics", metric_name, self.bench_dir)


def _in_cell(metric, cell_name):
    return "workloads" not in metric or cell_name in metric["workloads"]


def resolve(workload, benchmark_file=None, root=REPO_ROOT):
    """The :class:`Cell` named ``workload`` in the benchmark file
    (``BENCHMARK.json`` at ``root`` unless another is given)."""
    bench = read_json(benchmark_file
                      or os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; the benchmark has "
                       f"{sorted(cells)}")
    entry = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config_path = os.path.join(root, configs[entry["config"]]["file"])
    traffic_path = os.path.join(
        os.path.dirname(os.path.dirname(config_path)), "traffic",
        entry["traffic"] + ".json")
    return Cell(
        name=workload, chips=entry["chips"],
        config=read_json(config_path), traffic=read_json(traffic_path),
        end_to_end=[m for m in bench["end_to_end"]
                    if _in_cell(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _in_cell(m, workload)],
        bench_dir=os.path.join(root, bench["paths"][0]))
