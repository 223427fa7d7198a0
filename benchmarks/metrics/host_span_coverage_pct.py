"""Share of the window's wall, on the epoch-loop thread, that lies inside a
leaf `rw:` span: 100 less the time no span of the program accounts for."""
import spans


def read(run):
    p = spans.load()
    interval = p.window_interval() if p is not None else None
    if interval is None:
        return None
    share = spans.leaf_coverage(p.spans, *interval)
    return None if share is None else 100.0 * share
