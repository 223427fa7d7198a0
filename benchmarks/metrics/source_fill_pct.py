"""Live rows the source nodes emitted over the lanes they were handed
(every source walks the whole event-id range of an epoch and masks its own
table's rows), over the window's epochs: the rest of what every step down
to the aggs is handed is padding."""
import flow


def read(run):
    sources = flow.nodes("SourceNode")
    lanes = sum(n["lanes"] or 0 for n in sources) * run["epochs"]
    if not lanes:
        return None
    return 100.0 * sum(n["rows_out"] for n in sources) / lanes
