"""Seconds the dispatcher waited on pending compiles or cache loads
(`rw:compile_wait` spans) before the window's first barrier."""
import spans


def read(run):
    p = spans.load()
    if p is None:
        return None
    return spans.seconds(p.before_window("rw:compile_wait"))
