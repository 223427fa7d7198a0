"""Seconds of the `rw:sql` spans with a `create_*` kind before the window's
first barrier: CREATE SOURCE / CREATE MATERIALIZED VIEW of both passes
(parse, plan, fuse, state set-up)."""
import spans


def read(run):
    p = spans.load()
    if p is None:
        return None
    return spans.seconds(s for s in p.before_window("rw:sql")
                         if str(s.get("kind", "")).startswith("create_"))
