"""A configuration, a traffic mix, a cell and a metric added as new files
plus BENCHMARK.json entries are found; no file that was there is edited."""
import json
import os
import shutil

import discover


def test_new_files_are_picked_up(tmp_path):
    root = str(tmp_path)
    bench = os.path.join(root, "benchmarks")
    shutil.copytree(discover.HERE, bench,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    spec = discover.load_json(os.path.join(discover.ROOT, "BENCHMARK.json"))
    before = {os.path.join(d, f) for d, _, fs in os.walk(bench) for f in fs}

    with open(os.path.join(bench, "configs", "dummy-cfg.json"), "w") as f:
        json.dump({"name": "dummy-cfg", "events": 64,
                   "device": {"capacity": 64}, "checkpoint_frequency": 2,
                   "rehearse": {"events": 64}}, f)
    with open(os.path.join(bench, "configs", "dummy-cfg.py"), "w") as f:
        f.write("MV = 'dummy'\n")
    with open(os.path.join(bench, "traffic", "dummy-mix.json"), "w") as f:
        json.dump({"name": "dummy-mix", "chunk_size": 1, "epoch_events": 64,
                   "device": {}, "rehearse": {}}, f)
    with open(os.path.join(bench, "metrics", "dummy_metric.py"), "w") as f:
        f.write("def read(run):\n    return run['x'] * 2\n")
    spec["configs"].append({"name": "dummy-cfg", "source": "none",
                            "file": "benchmarks/configs/dummy-cfg.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "dummy.cell", "config": "dummy-cfg",
                              "traffic": "dummy-mix", "chips": 1,
                              "why": "test"})
    spec["per_layer"].append({"name": "dummy_metric", "unit": "count",
                              "better": "lower", "source": "program_counter",
                              "layer": "test", "moves": "events_per_s",
                              "workloads": ["dummy.cell"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)

    cell = discover.Cell("dummy.cell", root=root, bench_dir=bench)
    assert cell.config["events"] == 64 and cell.config_code.MV == "dummy"
    assert cell.traffic["epoch_events"] == 64 and cell.chips == 1
    readers = dict((m["name"], r) for m, r in cell.metrics("per_layer"))
    assert readers["dummy_metric"].read({"x": 21}) == 42
    # metrics that list other cells are not this cell's; unlisted ones are
    assert "h2d_mb_per_s" not in readers and "peak_hbm_mb" in readers
    # an old cell does not see the new metric
    old = discover.Cell("bid-agg.device", root=root, bench_dir=bench)
    assert "dummy_metric" not in [m["name"]
                                  for m, _ in old.metrics("per_layer")]
    for path in before:          # nothing that was there changed
        rel = os.path.relpath(path, bench)
        with open(path, "rb") as a, \
                open(os.path.join(discover.HERE, rel), "rb") as b:
            assert a.read() == b.read(), rel


def test_every_name_in_benchmark_json_has_its_files():
    spec = discover.load_json(os.path.join(discover.ROOT, "BENCHMARK.json"))
    for w in spec["workloads"]:
        cell = discover.Cell(w["name"])
        for kind in ("end_to_end", "per_layer"):
            for _m, reader in cell.metrics(kind):
                assert callable(reader.read)
        for fn in ("normalise", "reference", "control", "counts",
                   "least_bytes"):
            assert callable(getattr(cell.config_code, fn))
