"""Seconds jax spent reading executables from the persistent compile cache
before the window's first barrier (Σ `retrieval_s` of the compile spans
with `persistent == "hit"`)."""
import setup_spans
import spans


def read(run):
    loaded = setup_spans.loaded(spans.load())
    if loaded is None:
        return None
    return sum(s.get("retrieval_s", 0.0) for s in loaded)
