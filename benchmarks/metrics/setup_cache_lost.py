"""Executables the compile manifest knew (`cache_hit`) and jax built anew
all the same (`persistent == "miss"`: the span says `lost`) before the
window's first barrier: entries the persistent cache no longer held."""
import setup_spans
import spans


def read(run):
    found = setup_spans.compiles(spans.load())
    if found is None:
        return None
    return sum(1 for s in found if s.get("lost"))
