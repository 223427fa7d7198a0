"""Where one run's window goes, by the program's own spans:

    python benchmarks/tests/window_by_span.py <cell> <seed> [<seconds>] [--rehearse]

Runs the cell once in this process with `--trace 0`, as `run.py` does, and
prints, before the run's result line, one JSON row: the window and its two
end-to-end metrics beside the window's sums of the spans that can hold it
up — the host-fed stager's `rw:ingest.poll` / `.pack` / `.h2d`, the epoch
loop's `rw:ingest.wait` / `rw:dispatch` / `rw:device_sync`, the first tick,
the drain's `rw:commit.mirror` — and the interpreter's garbage collections
inside the window. Run it once a process and read the rows of several
seeds side by side: the span whose sum moves with the window carries the
run-to-run spread.
"""
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "lib"))

import discover  # noqa: E402
import run  # noqa: E402
import spans  # noqa: E402

SUMS = ("rw:ingest.poll", "rw:ingest.pack", "rw:ingest.h2d",
        "rw:ingest.wait", "rw:dispatch", "rw:device_sync",
        "rw:commit.mirror")


class GcClock:
    """Seconds and count of the interpreter's collections, by generation."""

    def __init__(self):
        self.t0, self.s, self.n = None, [0.0] * 3, [0] * 3
        self.on = False

    def __call__(self, phase, info):
        if not self.on:
            return
        if phase == "start":
            self.t0 = time.perf_counter()
        elif self.t0 is not None:
            g = info["generation"]
            self.s[g] += time.perf_counter() - self.t0
            self.n[g] += 1
            self.t0 = None


def main(workload, seed, seconds, rehearse=False):
    t_start = time.perf_counter()
    passes, clock = [], GcClock()
    gc.callbacks.append(clock)
    drive = run.window.drive

    def timed_drive(db, job, secs, annotate):
        clock.on = len(passes) == 1         # the second pass is the window
        out = drive(db, job, secs, annotate)
        clock.on = False
        passes.append(out)
        return out

    run.window.drive = timed_drive
    cell = discover.Cell(workload)
    result = run.run_cell(cell, seed, seconds, False, rehearse, t_start)
    win = passes[-1]
    p = spans.Passes(spans.ring())
    ticks = [t["t_done"] - t["t_admit"] for t in win["ticks"]]
    metric = {k: v["value"] for k, v in result["metrics"].items()}
    row = {"cell": workload, "seed": seed, "window_s": win["window_s"],
           "events_per_s": metric.get("events_per_s"),
           "event_latency_p95_ms": metric.get("event_latency_p95_ms"),
           "setup_s": metric.get("setup_s"), "correct": result["correct"],
           "tick0_s": ticks[0], "drain_s": sum(
               t["t_done"] - t["t_admit"] for t in win["ticks"]
               if t["label"].endswith(":drain")),
           "gc_s": clock.s, "gc_n": clock.n,
           "memory_peak_bytes": result["device"].get("memory_peak_bytes"),
           "ticks_s": ticks}
    for name in SUMS:
        row[name] = spans.seconds(p.of(p.window, name))
    print("ROW " + json.dumps(row), flush=True)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    args = [a for a in sys.argv[1:] if a != "--rehearse"]
    main(args[0], int(args[1]), float(args[2]) if len(args) > 2 else 50.0,
         "--rehearse" in sys.argv)
