"""Routing switches the window's job adopted (`FusedJob.rebalances`): each
is a rebuild and a replay of the committed history inside the window."""
import shards


def read(run):
    rep = shards.report()
    return None if rep is None else rep["rebalances"]
