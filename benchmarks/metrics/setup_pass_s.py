"""Seconds from the first to the last `rw:barrier` of the set-up pass's job
instance: the untimed pass of the stream (its compile waits lie inside)."""
import spans


def read(run):
    p = spans.load()
    bars = p.barriers(p.setup) if p is not None else []
    if not bars:
        return None
    return (bars[-1]["t1"] - bars[0]["t0"]) / 1e9
