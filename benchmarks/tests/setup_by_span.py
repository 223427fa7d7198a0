"""Where a run's `setup_s` went, read off the program's own spans.

Run by hand (on the chip, or with `--rehearse` on the CPU):
    python3 benchmarks/tests/setup_by_span.py --workload <cell> --seed <n>
        [--seconds 50] [--trace 0|1] [--rehearse] [--out FILE]
runs the cell once, in this process, exactly as `benchmarks/run.py` does
(`run.run_cell`), prints the run's result line, and then one JSON object:
  `timeline`  the thread that imported the program from the start of the
              process (`rw:boot`) to the window's first `rw:barrier`, in
              order: every top-level span (no parent) with its seconds, and
              every gap between two of them with its seconds and the spans
              on either side — the seconds no span of the program covers,
              each with an address;
  `setup`     `setup_s` (this process's start of the runner to the window's
              first barrier) beside the six set-up metrics of PR 36 and the
              three of PR 26; `rest_s`: the top-level spans and gaps after
              the first statement that are neither a CREATE nor inside the
              set-up pass; `sum_s` = `setup_boot_s` + `setup_create_s` +
              `setup_pass_s` + the rest, which is `setup_s` plus
              `process_start_to_runner_s`;
  `compiles`  every `rw:compile` / `rw:compile.inline` span that ended
              before the window (node or jax's `fun_name`, `persistent`,
              `backend_compile_s`, `retrieval_s`, `code_bytes`, `lost`);
  `jax`       `profile.COMPILES` (jax's own events, counted whatever span
              was open) as it stood when the window began and at the end,
              beside the same counts made from the spans: `built` /
              `loaded` / `lost` must agree.
A program without these spans (a commit before PR 36) prints `null`s.
"""
import argparse
import json
import os
import sys
import time

T_START, T_START_NS = time.perf_counter(), time.perf_counter_ns()
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(BENCH, "lib"))

import discover  # noqa: E402
import run as runner  # noqa: E402
import setup_spans  # noqa: E402
import spans as spans_lib  # noqa: E402
import window  # noqa: E402

SETUP_METRICS = ("setup_s", "setup_boot_s", "setup_create_s", "setup_pass_s",
                 "setup_await_s", "setup_compiles", "setup_compile_s",
                 "setup_cache_load_s", "setup_cache_lost",
                 "setup_span_coverage_pct")


def compile_counter():
    try:
        from risingwave_tpu.utils.profile import COMPILES
    except ImportError:
        return None
    return dict(COMPILES)


def segments(p, boot):
    """[(label, t0, t1, is a gap)] of the boot thread from the start of
    the process to the window's first barrier: its top-level spans (no
    parent) and the gaps of a millisecond or more between them."""
    tops = [s for s in p.spans if s["parent"] is None
            and s["thread"] == boot["thread"] and s["t0"] >= boot["t0"]
            and s["t0"] < p.t_window]
    out, upto, last = [], boot["t0"], "process start"
    for s in tops + [{"name": "the window's first rw:barrier",
                      "t0": p.t_window, "t1": p.t_window}]:
        if s["t0"] - upto > 1_000_000:
            out.append((f"gap: {last} .. {s['name']}", upto, s["t0"], True))
        last = s["name"] + (f" {s['kind']}" if s["name"] == "rw:sql" else "")
        if s["t1"] > s["t0"]:
            out.append((last, s["t0"], min(s["t1"], p.t_window), False))
        upto = max(upto, s["t1"])
    return out


def timeline(segs, t_zero):
    """The segments for print: seconds after the start of the process,
    seconds long; a pass's barriers as one line."""
    out = []
    for label, t0, t1, _gap in segs:
        if out and out[-1]["what"] == label:
            out[-1]["s"] += (t1 - t0) / 1e9
            out[-1]["n"] = out[-1].get("n", 1) + 1
        else:
            out.append({"at_s": (t0 - t_zero) / 1e9, "what": label,
                        "s": (t1 - t0) / 1e9})
    return out


def account(run):
    p = spans_lib.load()
    boot = setup_spans.boot(p)
    readings = {}
    for name in SETUP_METRICS:
        reader = discover.load_module(
            os.path.join(BENCH, "metrics", name + ".py"), "m_" + name)
        readings[name] = reader.read(run)
    if boot is None:
        return {"timeline": None, "setup": readings, "compiles": None}
    segs = segments(p, boot)
    first_sql = spans_lib.named(p.spans, "rw:sql")[0]["t0"]
    bars = p.barriers(p.setup)
    # what `setup_boot_s` + `setup_create_s` + `setup_pass_s` leave out:
    # after the first statement, outside the set-up pass's barriers, and
    # not a CREATE
    rest = [(label, t0, t1) for label, t0, t1, _gap in segs
            if t0 >= first_sql and not label.startswith("rw:sql create_")
            and not (bars and bars[0]["t0"] <= t0 < bars[-1]["t1"])]
    readings["rest_s"] = {}
    for label, t0, t1 in rest:
        readings["rest_s"][label] = (readings["rest_s"].get(label, 0.0)
                                     + (t1 - t0) / 1e9)
    readings["sum_s"] = (readings["setup_boot_s"] + readings["setup_create_s"]
                         + readings["setup_pass_s"]
                         + sum(readings["rest_s"].values()))
    readings["process_start_to_runner_s"] = \
        (run["t_start_ns"] - boot["t0"]) / 1e9
    found = setup_spans.compiles(p) or []
    keys = ("name", "node", "fun_name", "persistent", "backend_compile_s",
            "retrieval_s", "trace_s", "lower_s", "code_bytes", "lost",
            "cache_hit", "tname")
    return {"timeline": timeline(segs, boot["t0"]), "setup": readings,
            "compiles": [dict({k: s[k] for k in keys if k in s},
                              s=(s["t1"] - s["t0"]) / 1e9)
                         for s in sorted(found, key=lambda s: s["t0"])],
            "by_spans": {
                "built": len(setup_spans.built(p) or []),
                "loaded": len(setup_spans.loaded(p) or []),
                "lost": sum(1 for s in found if s.get("lost"))}}


def main(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=50.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", help="also write the account to this file")
    args = ap.parse_args(argv)
    snaps, drive = [], window.drive

    def counted_drive(*a, **kw):
        snaps.append(compile_counter())     # [set-up pass, window] starts
        return drive(*a, **kw)

    window.drive = counted_drive
    cell = discover.Cell(args.workload)
    # the readers see `run` only through run_cell's locals: keep what the
    # account needs by reading the same spans, and setup_s off the result
    result = runner.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                             args.rehearse, T_START)
    window.drive = drive
    print(json.dumps(result), flush=True)
    p = spans_lib.load()
    run = {"setup_s": None, "t_start_ns": T_START_NS}
    if p is not None and p.t_window is not None:
        # the runner stops its set-up clock just before the window's
        # first tick: the first barrier's start, to the millisecond
        run["setup_s"] = (p.t_window - run["t_start_ns"]) / 1e9
    out = account(run)
    out["jax"] = {"at_window_start": snaps[1] if len(snaps) > 1 else None,
                  "at_end": compile_counter()}
    out["cell"], out["seed"] = args.workload, args.seed
    text = json.dumps(out, indent=1, default=str)
    print(text, flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            f.write(text + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
