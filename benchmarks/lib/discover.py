"""Find a cell's files by the names BENCHMARK.json gives: nothing is listed
in code, so a configuration, a traffic mix or a metric is added as files."""
import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(path, name):
    """Import one file by path (file names carry `-` and `.`)."""
    lib = os.path.join(HERE, "lib")
    if lib not in sys.path:
        sys.path.insert(0, lib)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _entry(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"BENCHMARK.json names no {what} {name!r}: "
                     f"{[e['name'] for e in entries]}")


class Cell:
    """One entry of BENCHMARK.json's `workloads` with everything it names."""

    def __init__(self, workload, root=ROOT, bench_dir=HERE):
        self.bench = load_json(os.path.join(root, "BENCHMARK.json"))
        self.entry = _entry(self.bench["workloads"], workload, "workload")
        self.name = workload
        self.chips = int(self.entry["chips"])
        cfg = _entry(self.bench["configs"], self.entry["config"],
                     "configuration")
        self.config = load_json(os.path.join(root, cfg["file"]))
        self.config_code = load_module(
            os.path.splitext(os.path.join(root, cfg["file"]))[0] + ".py",
            "bench_config")
        self.traffic = load_json(os.path.join(
            bench_dir, "traffic", self.entry["traffic"] + ".json"))
        self.bench_dir = bench_dir

    def metrics(self, kind):
        """[(entry, reader module)] of this cell's `end_to_end` or
        `per_layer` metrics; a metric without `workloads` is every cell's."""
        out = []
        for m in self.bench[kind]:
            if "workloads" in m and self.name not in m["workloads"]:
                continue
            path = os.path.join(self.bench_dir, "metrics", m["name"] + ".py")
            out.append((m, load_module(path, "bench_metric_" + m["name"])))
        return out
