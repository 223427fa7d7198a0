"""The benchmark's one command: one cell, one run, one process.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Drives the served SQL path (CREATE SOURCE / CREATE MATERIALIZED VIEW /
Database.tick / FusedJob.sync / Database.query) of the cell's configuration
under its traffic, on the TPU the machine holds, and prints one JSON object
as the last line of stdout. Everything that belongs to one configuration,
traffic mix or metric is a file found by the name BENCHMARK.json gives
(lib/discover.py); README.md says how to add one.

`--rehearse` is the CPU rehearsal of the same functions at the tiny sizes the
files give under `rehearse`: it stamps the platform it ran on and prints no
number under a metric's name.
"""
import argparse
import collections
import contextlib
import gc
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, os.path.join(HERE, "lib"))

import discover  # noqa: E402
import trace as trace_lib  # noqa: E402
import window  # noqa: E402

IDLE_TIMEOUT_S = 900.0
OUT_DIR = os.path.join(ROOT, ".bench_out")


def note(msg):
    print(f"[{time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr, flush=True)


def _numbers(layer):
    """The whole numbers a file sets at its top level and under `device`:
    its sizes (a flag such as `host_ingest` is not one)."""
    out = {k for k, v in layer.items()
           if isinstance(v, int) and not isinstance(v, bool)}
    return out | {"device." + k for k, v in layer.get("device", {}).items()
                  if isinstance(v, int) and not isinstance(v, bool)}


def sizes(cell, rehearse):
    """Event count, chunk size, DeviceConfig fields: the files' real sizes,
    or their `rehearse` blocks. Fields are laid over each other in the order
    configuration, its `rehearse`, traffic, its `rehearse`, so a traffic
    file's `events` or device field wins over the configuration's. A
    traffic file names a rehearsal size for every number it sets: its real
    sizes never reach a rehearsal."""
    cfg, tr = cell.config, cell.traffic
    layers = [cfg, tr]
    if rehearse:
        small = tr["rehearse"]
        unsized = sorted(_numbers(tr) - _numbers(small))
        if unsized:
            raise SystemExit(f"traffic {tr['name']} sets {unsized} but its "
                             "rehearse block does not")
        layers = [cfg, cfg["rehearse"], tr, small]
    merged, device = {}, {}
    for layer in layers:
        merged.update(layer)
        device.update(layer.get("device", {}))
    return {"events": merged["events"], "chunk": merged["chunk_size"],
            "epoch_events": merged["epoch_events"], "device": device,
            "checkpoint_frequency": cfg["checkpoint_frequency"]}


def create(cell, sz, seed):
    """A fresh Database with the cell's sources and MV; the seed reaches
    the data through the generator the sources share."""
    from risingwave_tpu.config import DeviceConfig
    from risingwave_tpu.connectors.nexmark import (NexmarkConfig,
                                                   NexmarkGenerator)
    from risingwave_tpu.sql import Database
    code = cell.config_code
    db = Database(device=DeviceConfig(**sz["device"]),
                  checkpoint_frequency=sz["checkpoint_frequency"])
    db._nexmark_gen = NexmarkGenerator(NexmarkConfig(seed=seed))
    for sql in code.SOURCES:
        db.run(sql.format(events=sz["events"], chunk=sz["chunk"]))
    db.run(code.MV_SQL)
    job = db.catalog.get(code.MV).runtime["fused_job"]
    if job is None:
        raise SystemExit(f"{code.MV}: the MV is not fused (host executors); "
                         "this benchmark measures the fused device path")
    if job.program.epoch_events != sz["epoch_events"]:
        raise SystemExit(f"epoch of {job.program.epoch_events} events, the "
                         f"traffic file says {sz['epoch_events']}")
    return db, job


def drop(db, job):
    """Let go of a Database and its device state."""
    if job.ingest is not None:
        job.ingest.close()
    job.states = job.snapshot = None
    del db, job
    gc.collect()


def state_leaves_off(job, platform):
    import jax
    return sum(1 for leaf in jax.tree_util.tree_leaves(job.states)
               for d in leaf.devices() if d.platform != platform)


def multiset_diff(got, want):
    got, want = collections.Counter(got), collections.Counter(want)
    return sum((want - got).values()), sum((got - want).values())


def memory_peak(devs):
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)


def run_cell(cell, seed, seconds, trace, rehearse=False, t_start=None,
             keep_trace=False):
    """One run of one cell; returns the result object."""
    t_start = time.perf_counter() if t_start is None else t_start
    sys.path.insert(0, ROOT)
    try:
        import risingwave_tpu.device  # noqa: F401  (x64, compile cache)
    except ImportError as e:
        raise SystemExit(f"the program is not in this checkout: {e}")
    import jax
    from risingwave_tpu.device import compile_cache_dir
    from risingwave_tpu.device.compile_service import get_service

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    print(f"device: {device['platform']} / {device['kind']} / "
          f"{device['count']}", flush=True)
    if not rehearse and (device["platform"] != "tpu"
                         or device["count"] < cell.chips):
        raise SystemExit(f"{cell.name} needs {cell.chips} tpu device(s); "
                         f"refusing to run on {device}")
    peaks = discover.load_json(os.path.join(HERE, "lib", "peaks.json"))
    if not rehearse and device["kind"] not in peaks:
        raise SystemExit(f"no peaks for device kind {device['kind']!r} in "
                         "lib/peaks.json")
    print(f"compile cache: {compile_cache_dir()}", flush=True)
    sz = sizes(cell, rehearse)
    code, svc = cell.config_code, get_service()
    annotate = jax.profiler.TraceAnnotation

    # ---- set-up: one untimed pass of the same stream in a scratch
    # Database; the process-global compile service keeps every program
    db, job = create(cell, sz, seed)
    warm = window.drive(db, job, float("inf"), contextlib.nullcontext)
    drop(db, job)
    if not svc.wait_idle(IDLE_TIMEOUT_S):
        raise SystemExit(f"compile service busy after {IDLE_TIMEOUT_S} s")
    aot_setup = svc.summary()
    note(f"set-up pass {warm['window_s']:.1f} s, compile service {aot_setup}")
    db, job = create(cell, sz, seed)
    trace_dir = os.path.join(OUT_DIR, f"trace-{cell.name}-{seed}")
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_lib.start(trace_dir)
    setup_s = time.perf_counter() - t_start

    # ---- the window
    with annotate("window"):
        win = window.drive(db, job, seconds, annotate)
    if trace:
        jax.profiler.stop_trace()
    aot_win = svc.summary()
    memory_peak_bytes = memory_peak(devs)

    # ---- after the clock: read the MV, the counters, then the reference
    t0 = time.perf_counter()
    got = code.normalise(db.query(code.READ_SQL))
    read_s = time.perf_counter() - t0
    delta = {k: aot_win[k] - aot_setup[k]
             for k in ("compiles", "failed", "inline_steps",
                       "compiled_steps", "cache_hits")}
    ticks = win["ticks"]
    profile = job.profiler.summary()
    platform = "cpu" if rehearse else "tpu"
    run = {
        "window_s": win["window_s"], "ticks": ticks, "setup_s": setup_s,
        "events_committed": win["events_committed"],
        "epochs": sum(1 for t in ticks if t["events"] > 0),
        "checkpoints": len({t["committed"] for t in ticks} - {0}),
        "phase_s": profile["phase_s"],
        "growth_replays": job.growth_replays,
        "window_compiles": (delta["compiles"] + delta["failed"]
                            + delta["inline_steps"]),
        "memory_peak_bytes": memory_peak_bytes,
        "peaks": peaks.get(device["kind"]),
    }
    if job.ingest is not None:
        run["ingest"] = dict(job.ingest.stats(), feed_bytes=sum(
            a.nbytes for a in jax.tree_util.tree_leaves(job.ingest._bufs[0])
            if hasattr(a, "nbytes")))
    checks = {
        "events_uncommitted": job.counter - job.committed,
        "state_leaves_off_" + platform: state_leaves_off(job, platform),
        "recoveries": job.recoveries,
        "barriers_replayed": sum(1 for t in ticks if t["recovered"]),
        "compiles_failed": delta["failed"],
        "inline_steps": delta["inline_steps"],
        "steps_compiled_none": int(delta["compiled_steps"] <= 0),
    }
    cap = job.cap_report()
    consumed = job.counter
    drop(db, job)
    t0 = time.perf_counter()
    want = code.reference(seed, consumed)
    checks["rows_missing"], checks["rows_unexpected"] = multiset_diff(got,
                                                                      want)
    ref_s = time.perf_counter() - t0
    if trace:
        run["least_bytes"] = code.least_bytes(
            code.counts(seed, win["events_committed"], sz["epoch_events"]))
        records = trace_lib.load(trace_lib.find_xplane(trace_dir))
        run["trace"] = trace_lib.reduce(records)
        if not keep_trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
        if not rehearse and not run["trace"]:
            raise SystemExit("the trace holds no device operation")

    # ---- report (a worker thread still inside a compile would abort the
    # interpreter's exit: wait for the service first)
    svc.wait_idle(IDLE_TIMEOUT_S)
    note(f"compile service at the end {svc.summary()}")
    print(json.dumps({"phase_s": run["phase_s"], "aot_window": delta,
                      "compile_events": profile["compile_events"], "cap": cap,
                      "ingest": run.get("ingest"), "rows": len(got),
                      "read_s": read_s, "reference_s": ref_s,
                      "events_consumed": consumed,
                      "ticks": [[t["label"], round(t["t_admit"], 4),
                                 round(t["t_done"], 4), t["events"],
                                 t["committed"]] for t in ticks]},
                     default=str), flush=True)
    metrics = {}
    for entry, reader in cell.metrics("per_layer" if trace else "end_to_end"):
        value = reader.read(run)
        if value is not None:
            metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}
    correct = all(v == 0 for v in checks.values())
    compared = {k: {"value": v, "limit": 0} for k, v in checks.items()}
    for k, v in compared.items():
        print(f"compared {k}: {v['value']} (limit {v['limit']})",
              file=sys.stderr, flush=True)
    result = {"correct": correct, "attempted": len(ticks),
              "failed": checks["barriers_replayed"]}
    if rehearse:
        # a CPU run gives counts, never a time, a rate or a share
        result.update(rehearsal=True, metrics={},
                      metric_names=sorted(metrics),
                      counts={"events_committed": win["events_committed"],
                              "epochs": run["epochs"],
                              "checkpoints": run["checkpoints"],
                              "growth_replays": run["growth_replays"],
                              "window_compiles": run["window_compiles"]})
    else:
        result["metrics"] = metrics
        device["memory_peak_bytes"] = memory_peak_bytes
        if trace:
            device["busy_s"] = run["trace"]["busy_s"]
            device["window_s"] = win["window_s"]
            result["breakdown"] = {
                "device_ops": run["trace"]["device_ops"],
                "idle_gaps": run["trace"]["idle_gaps"]}
    result["device"] = device
    result["compared"] = compared
    return result


def main(argv):
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at the files' tiny sizes")
    ap.add_argument("--keep-trace", action="store_true",
                    help="leave the trace under .bench_out for a look by hand")
    args = ap.parse_args(argv)
    cell = discover.Cell(args.workload)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      args.rehearse, t_start, args.keep_trace)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
