"""The program's own spans (`risingwave_tpu.utils.profile.SPANS`, names
`rw:...`, clock `time.perf_counter_ns`) cut to the two passes of a run,
for the per-layer metrics that read them.

A run makes two instances of the cell's job: the set-up pass (a scratch
Database) and the window. The newest job instance that has an `rw:epoch`
span is the window's, the one before it the set-up pass's. A barrier
belongs to the instance whose spans sit under it. A program without a span
ring (a commit before the spans) reads as nothing: `load()` gives `None`
and every reader leaves its metric out.
"""


def ring():
    """The finished spans of this process, or `None` where the program has
    no span ring."""
    try:
        from risingwave_tpu.utils.profile import SPANS
    except ImportError:
        return None
    return list(SPANS)


def seconds(spans):
    return sum(s["t1"] - s["t0"] for s in spans) / 1e9


def named(spans, name):
    return [s for s in spans if s["name"] == name]


def roots(spans):
    """{span id: the span at the top of its parent chain} (a parent that
    has left the ring ends the chain)."""
    by_id = {s["id"]: s for s in spans}
    out = {}
    for s in spans:
        top = s
        while top["parent"] in by_id:
            top = by_id[top["parent"]]
        out[s["id"]] = top
    return out


class Passes:
    """The spans of one run, cut: `window` and `setup` are the two job
    instances' numbers, `barriers(inst)` an instance's `rw:barrier` spans
    in time order, `t_window` the start of the window's first barrier."""

    def __init__(self, spans):
        self.spans = sorted(spans, key=lambda s: s["t0"])
        insts = sorted({s["inst"] for s in named(self.spans, "rw:epoch")
                        if "inst" in s})
        self.window = insts[-1] if insts else None
        self.setup = insts[-2] if len(insts) > 1 else None
        self._roots = roots(self.spans)
        first = self.barriers(self.window)
        self.t_window = first[0]["t0"] if first else None

    def of(self, inst, name=None):
        """An instance's spans, all or those of one name."""
        if inst is None:
            return []
        return [s for s in self.spans if s.get("inst") == inst
                and (name is None or s["name"] == name)]

    def barriers(self, inst):
        seen = {}
        for s in self.of(inst):
            top = self._roots[s["id"]]
            if top["name"] == "rw:barrier":
                seen[top["id"]] = top
        return sorted(seen.values(), key=lambda s: s["t0"])

    def before_window(self, name):
        """Spans of `name` that ended before the window's first barrier
        began: the set-up's."""
        if self.t_window is None:
            return []
        return [s for s in named(self.spans, name)
                if s["t1"] <= self.t_window]

    def window_interval(self):
        """(thread, start, end) of the window on the epoch-loop thread:
        from the start of its first barrier to the end of its last barrier
        or parentless span (the closing `sync()` after the last barrier is
        one), whichever is later."""
        bars = self.barriers(self.window)
        if not bars:
            return None
        thread = bars[0]["thread"]
        loose = [s for s in self.of(self.window) if s["parent"] is None
                 and s["thread"] == thread and s["t0"] >= bars[0]["t0"]]
        return thread, bars[0]["t0"], max(s["t1"] for s in bars + loose)


def load():
    """The run's `Passes`, or `None` where there is nothing to read."""
    spans = ring()
    if not spans:
        return None
    passes = Passes(spans)
    return passes if passes.window is not None else None


def leaf_coverage(spans, thread, start, end):
    """Share (0..1) of [start, end) on `thread` that lies inside a leaf
    span: one no other span names as its parent."""
    parents = {s["parent"] for s in spans}
    cover = sorted((max(s["t0"], start), min(s["t1"], end)) for s in spans
                   if s["thread"] == thread and s["id"] not in parents
                   and s["t1"] > start and s["t0"] < end)
    inside, upto = 0, start
    for s, e in cover:
        if e > upto:
            inside += e - max(s, upto)
            upto = e
    return inside / (end - start) if end > start else None
