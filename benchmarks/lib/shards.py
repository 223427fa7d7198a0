"""The window job's shard report (`FusedJob.shard_report()`), as the
program leaves it on the `rw:commit.gauges` span of every checkpoint of a
mesh-sharded job: per exchange stage `exch`, `slots` and the live rows each
shard received (`rows_in`, summed over the job's epochs), per keyed node
the live entries of each shard (`live`), and `rebalances`. A program that
leaves none (one chip, or a commit before the report) reads as `None`."""
import spans


def report():
    """The report of the window's last checkpoint, or `None`."""
    p = spans.load()
    if p is None:
        return None
    found = [s["shard_report"] for s in p.of(p.window, "rw:commit.gauges")
             if s.get("shard_report")]
    return found[-1] if found else None
