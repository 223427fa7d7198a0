"""Share of the set-up's wall, on the thread that imported the program
(the epoch loop's), that lies inside a leaf `rw:` span: from the start of
the process (`rw:boot`) to the window's first barrier; 100 less the time no
span of the program accounts for (the runner's own calls, and the self time
of spans that have children)."""
import setup_spans
import spans


def read(run):
    p = spans.load()
    boot = setup_spans.boot(p)
    if boot is None or p.t_window is None:
        return None
    share = spans.leaf_coverage(p.spans, boot["thread"], boot["t0"],
                                p.t_window)
    return None if share is None else 100.0 * share
