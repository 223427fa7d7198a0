"""Seconds jax's backend spent building those programs (Σ
`backend_compile_s` of the spans `setup_compiles` counts): work of the
compile service's worker pool; the wall it cost the epoch loop is
`setup_await_s`."""
import setup_spans
import spans


def read(run):
    built = setup_spans.built(spans.load())
    if built is None:
        return None
    return sum(s["backend_compile_s"] for s in built)
