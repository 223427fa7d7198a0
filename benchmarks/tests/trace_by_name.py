"""What a kept device trace says once the program names what it runs.

Run by hand on a trace that `run.py --trace 1 --keep-trace` left:
    python benchmarks/tests/trace_by_name.py <trace dir or .xplane.pb>
prints, for the first device plane that ran anything,
  (i)   device seconds per XLA module (`jit_step_<node>`, `jit_stats_fold`,
        ...): the `XLA Modules` line summed by name;
  (ii)  device seconds per named scope (`agg.merge/merge.sort`, ...): every
        operation's OWN time (`trace.self_times`) under the scope path of
        its op_name, `jit(...)` wrappers and the operation's own name
        taken off. On a v5e the op_name is the `tf_op` stat of the event's
        METADATA (`jit(step_agg_k0)/agg.merge/merge.sort/sort:`), which
        `jax.profiler.ProfileData` does not show: this table reads the
        file with the profiler's own protobuf (`tensorflow.tsl`), and is
        left out with a note where that cannot be imported. Operations
        under no scope are listed by the source line their metadata names
        (`source`), so that unscoped device time still has an address;
  (iii) idle gaps charged to the innermost span of the epoch-loop thread,
        the program's `rw:` spans included: `trace.load` and `trace.reduce`
        as the benchmark runs them, with `rw:` added to the names kept.
  (iv)  host seconds of the program's spans inside the runner's `window`,
        by thread and name, with `rw:step` split by its position in its
        `rw:dispatch` (= the node's index in the program): which call of
        the epoch loop the host was blocked in.
"""
import json
import os
import re
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", "lib"))
import trace as trace_lib  # noqa: E402

# the stats of a device operation's metadata that hold its op_name and
# the source line it was traced from
SCOPE_STAT = "tf_op"
SOURCE_STAT = "source"
# components of an op_name that say how it was traced, not where it belongs
STRUCTURAL = {"while", "body", "cond", "closed_call", "checkpoint", "remat",
              "pjit", "custom_jvp_call", "custom_vjp_call"}
PROGRAM_SPANS = ("rw:",)


def module_name(event_name):
    """`jit_step_agg_k0(123)` -> `jit_step_agg_k0`."""
    return re.sub(r"\(\d+\)$", "", event_name)


def scope_path(op_name):
    """`jit(step_x)/jit(main)/agg.merge/merge.sort/sort` ->
    `agg.merge/merge.sort`; `""` for an operation under no scope. An inner
    jit repeats the scopes around it (`join.probe/join.probe/while/body`):
    structural components go and a name counts once."""
    parts = [p for p in op_name.rstrip(":").split("/")
             if p and not re.fullmatch(r"\w+\(.*\)", p)][:-1]
    return "/".join(dict.fromkeys(p for p in parts if p not in STRUCTURAL))


def module_table(path):
    """({module: s}, modules_s) of the first device plane that ran any."""
    from jax.profiler import ProfileData
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith(trace_lib.DEVICE_PLANE):
            continue
        modules = {}
        for line in plane.lines:
            if line.name == trace_lib.MODULES_LINE:
                for ev in line.events:
                    name = module_name(ev.name)
                    modules[name] = modules.get(name, 0) + ev.duration_ns
        if modules:
            return ({k: v / 1e9 for k, v in modules.items()},
                    sum(modules.values()) / 1e9)
    return {}, 0.0


def scope_table(path):
    """({scope path: s of its operations' own time}, {source line: s of the
    unscoped operations' own time}) of the first device plane with
    operations; `None` where the protobuf cannot be imported."""
    try:
        from tensorflow.tsl.profiler.protobuf import xplane_pb2
    except ImportError:
        return None
    space = xplane_pb2.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    for plane in space.planes:
        if not plane.name.startswith(trace_lib.DEVICE_PLANE):
            continue
        ids = {v.name: k for k, v in plane.stat_metadata.items()}
        op_names, sources = {}, {}
        for mid, md in plane.event_metadata.items():
            for st in md.stats:
                for stat, into in ((SCOPE_STAT, op_names),
                                   (SOURCE_STAT, sources)):
                    if st.metadata_id == ids.get(stat):
                        into[mid] = st.str_value or \
                            plane.stat_metadata[st.ref_value].name
        ops = []
        for line in plane.lines:
            if line.name != trace_lib.OPS_LINE:
                continue
            for ev in line.events:
                if ev.duration_ps > 0:
                    # offsets within the line: enough to nest operations
                    ops.append((ev.offset_ps, ev.duration_ps,
                                (scope_path(op_names.get(ev.metadata_id,
                                                         "")),
                                 sources.get(ev.metadata_id, "?"),
                                 len(ops))))
        if not ops:
            continue
        scopes, unscoped = {}, {}
        for (scope, source, _i), ps in trace_lib.self_times(ops).items():
            scopes[scope or "(no scope)"] = \
                scopes.get(scope or "(no scope)", 0) + ps
            if not scope:
                unscoped[source] = unscoped.get(source, 0) + ps
        return ({k: v / 1e12 for k, v in scopes.items()},
                {k: v / 1e12 for k, v in unscoped.items()})
    return {}, {}


def idle_gaps(path):
    """`trace.reduce` over the device planes and the epoch-loop thread's
    spans (the line that holds the runner's `window`), `rw:` included."""
    records = trace_lib.load(
        path, keep_host=trace_lib.RUNNER_SPANS + PROGRAM_SPANS)
    loop = {(p, l) for p, l, n, _s, _d in records if n == "window"}
    records = [r for r in records
               if r[0].startswith(trace_lib.DEVICE_PLANE)
               or (r[0], r[1]) in loop]
    return trace_lib.reduce(records, top=20)


def host_spans(path):
    """{(thread line, span name): [count, s]} of the `rw:` spans inside the
    `window` span; a `rw:step` is named `rw:step[i]` after its position
    among the steps of the `rw:dispatch` (or `rw:growth`) around it."""
    records = trace_lib.load(
        path, keep_host=("window",) + PROGRAM_SPANS)
    win = [(s, s + d) for _p, _l, n, s, d in records if n == "window"]
    lo, hi = win[0] if win else (0, float("inf"))
    by_line = {}
    for p, l, n, s, d in records:
        if n.startswith(PROGRAM_SPANS) and lo <= s and s + d <= hi:
            by_line.setdefault(l, []).append((s, s + d, n))
    out = {}
    for line, spans in by_line.items():
        spans.sort()
        frames = sorted((s, e) for s, e, n in spans
                        if n in ("rw:dispatch", "rw:growth"))
        seen = {}
        for s, e, n in spans:
            if n == "rw:step":
                frame = max((f for f in frames if f[0] <= s and e <= f[1]),
                            default=None)        # the innermost around it
                # a replay under rw:growth dispatches every epoch's steps
                # in one frame: the position wraps at the first repeat
                k = seen[frame] = seen.get(frame, -1) + 1
                n = f"rw:step[{k}]"
            rec = out.setdefault((line, n), [0, 0.0])
            rec[0] += 1
            rec[1] += (e - s) / 1e9
    return out


def table(title, rows, total=None, top=None):
    print(title)
    for name, s in sorted(rows.items(), key=lambda kv: -kv[1])[:top]:
        print(f"  {s:10.4f} s  {name}")
    if total is not None:
        print(f"  {sum(rows.values()):10.4f} s  sum   (modules_s {total:.4f})")


def main(argv):
    path = argv[0]
    if os.path.isdir(path):
        path = trace_lib.find_xplane(path)
    modules, modules_s = module_table(path)
    read = scope_table(path)
    scopes, unscoped = read if read is not None else (None, None)
    table("device seconds by XLA module", modules, modules_s)
    if scopes is None:
        print("device seconds by named scope: not read (no "
              "tensorflow.tsl protobuf to read event metadata with)")
    else:
        table("device seconds by named scope (operations' own time)",
              scopes)
        table("unscoped device seconds by source line (top 12)", unscoped,
              top=12)
    reduced = idle_gaps(path)
    print("idle gaps by innermost span of the epoch-loop thread")
    for name, s in (reduced or {}).get("idle_gaps", []):
        print(f"  {s:10.4f} s  {name}")
    print("host seconds of the program's spans in the window, by thread")
    spans = host_spans(path)
    for (line, name), (n, sec) in sorted(spans.items(),
                                         key=lambda kv: (kv[0][0], -kv[1][1])):
        if sec >= 0.0005:
            print(f"  {sec:10.4f} s  {n:5d} x  {name:32s} {line}")
    print(json.dumps({"modules": modules, "modules_s": modules_s,
                      "scopes": scopes, "unscoped_by_source": unscoped,
                      "idle_gaps": (reduced or {}).get("idle_gaps"),
                      "host_spans": [[l, n, c, sec] for (l, n), (c, sec)
                                     in sorted(spans.items())]}))


if __name__ == "__main__":
    main(sys.argv[1:])
