"""Programs jax BUILT before the window's first barrier: `rw:compile` and
`rw:compile.inline` spans whose `persistent` is not `hit` and whose
backend compile took a second or more (an eager primitive is not a
program). A warm machine and a seed seen read 0."""
import setup_spans
import spans


def read(run):
    built = setup_spans.built(spans.load())
    return None if built is None else len(built)
