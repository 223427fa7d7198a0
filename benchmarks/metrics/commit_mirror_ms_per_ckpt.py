"""Host milliseconds of the `rw:commit.mirror` spans of the window (the MV
pulled to the host, diffed and written to its state table) per checkpoint
that committed in the window."""
import spans


def read(run):
    p = spans.load()
    if p is None or not run["checkpoints"]:
        return None
    mirror = p.of(p.window, "rw:commit.mirror")
    return spans.seconds(mirror) / run["checkpoints"] * 1e3
