"""Reduction of a JAX profiler trace (.xplane.pb) to the numbers the
per-layer metrics read. Kept with the benchmark so that every PR computes
them the same way; checked on the small recorded trace in tests/data.

A trace is reduced in two steps: `load` turns the file into plain records
(plane, line, name, start_ns, dur_ns) and `reduce` turns records into
numbers, so that the second step can be tested without the profiler.
"""
import glob
import os
import re

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# the runner's own spans (TraceAnnotation), on a host thread's line
RUNNER_SPANS = ("tick:", "sync", "window")


def start(trace_dir):
    """Start a profiler session that keeps device events and the runner's
    `TraceAnnotation` spans, and no Python call stacks."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def find_xplane(trace_dir):
    """The one .xplane.pb a `jax.profiler` session left under `trace_dir`."""
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path, keep_host=RUNNER_SPANS):
    """[(plane, line, name, start_ns, dur_ns)]: every event of the device
    planes, and of the host planes the runner's own spans only."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith(DEVICE_PLANE)
        for line in plane.lines:
            for ev in line.events:
                if device or ev.name.startswith(keep_host):
                    out.append((plane.name, line.name, ev.name,
                                int(ev.start_ns), int(ev.duration_ns)))
    return out


def describe(path, top=12):
    """What a trace holds, for a look by hand: planes, lines, event counts,
    seconds and the longest names."""
    from jax.profiler import ProfileData
    lines = []
    for plane in ProfileData.from_file(path).planes:
        lines.append(f"plane {plane.name!r}")
        for line in plane.lines:
            tot, n, first, last = {}, 0, None, 0
            for ev in line.events:
                n += 1
                tot[ev.name] = tot.get(ev.name, 0) + ev.duration_ns
                first = ev.start_ns if first is None else min(first,
                                                              ev.start_ns)
                last = max(last, ev.start_ns + ev.duration_ns)
            lines.append(f"  line {line.name!r}: {n} events, "
                         f"{sum(tot.values()) / 1e9:.4f} s, span "
                         f"{first}..{last}")
            for name, ns in sorted(tot.items(), key=lambda kv: -kv[1])[:top]:
                lines.append(f"    {ns / 1e9:10.4f} s  {name[:140]}")
    return "\n".join(lines)


def _union(intervals):
    """Merged, sorted [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def short_name(hlo):
    """`%fusion.7 = u32[...] fusion(...)` -> `%fusion.7 fusion`: the
    profiler names a device operation by its whole HLO text."""
    head, sep, rest = hlo.partition(" = ")
    if not sep:
        return hlo[:80]
    m = re.search(r"(?:^|[\s)])([a-z][a-z0-9\-]*)\(", rest)
    return f"{head} {m.group(1)}" if m else head[:80]


def self_times(events):
    """{name: ns} with every operation's own time: its duration less that of
    the operations nested inside it on the same line (a `while` holds the
    operations of its body)."""
    out, stack = {}, []           # stack of [end, name, self_ns]

    def close(upto):
        while stack and stack[-1][0] <= upto:
            _e, name, own = stack.pop()
            out[name] = out.get(name, 0) + max(own, 0)

    for s, d, name in sorted(events, key=lambda e: (e[0], -e[1])):
        close(s)
        if stack:
            stack[-1][2] -= d
        stack.append([s + d, name, d])
    close(float("inf"))
    return out


def reduce(records, top=10):
    """Numbers of one traced window. Device planes are averaged over the
    chips that ran anything; a trace in which no device operation ran
    reduces to nothing (`None`). Idle gaps are those of the first device
    plane, between the start and the end of the runner's `window` span, each
    charged to the innermost runner span that covers its middle."""
    planes = sorted({p for p, *_ in records if p.startswith(DEVICE_PLANE)})
    spans = sorted((s, s + d, n) for p, _l, n, s, d in records
                   if not p.startswith(DEVICE_PLANE))
    whole = [(s, e) for s, e, n in spans if n == "window"]
    busy_ns, module_ns, gaps, ops = [], [], [], {}
    for plane in planes:
        events = [(s, d, n) for p, l, n, s, d in records
                  if p == plane and l == OPS_LINE and d > 0]
        if not events:
            continue
        merged = _union((s, s + d) for s, d, _n in events)
        busy_ns.append(sum(e - s for s, e in merged))
        module_ns.append(sum(d for p, l, _n, _s, d in records
                             if p == plane and l == MODULES_LINE))
        for name, ns in self_times(events).items():
            ops[name] = ops.get(name, 0) + ns
        if plane == planes[0]:
            if whole:
                merged = ([[whole[0][0]] * 2] + merged + [[whole[0][1]] * 2])
            for (_s0, e0), (s1, _e1) in zip(merged, merged[1:]):
                if s1 > e0:
                    gaps.append((s1 - e0, (e0 + s1) // 2))
    if not busy_ns:
        return None
    by_span = {}
    for ns, mid in gaps:
        cover = [(e - s, n) for s, e, n in spans if s <= mid < e]
        name = min(cover)[1] if cover else "outside the runner's spans"
        by_span[name] = by_span.get(name, 0) + ns
    n = len(busy_ns)
    return {
        "chips_traced": n,
        "busy_s": sum(busy_ns) / n / 1e9,
        "modules_s": sum(module_ns) / n / 1e9,
        "device_ops": [[short_name(k), v / n / 1e9] for k, v in sorted(
            ops.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[k, v / 1e9] for k, v in sorted(
            by_span.items(), key=lambda kv: -kv[1])[:top]],
    }


if __name__ == "__main__":
    import sys
    print(describe(sys.argv[1]))
