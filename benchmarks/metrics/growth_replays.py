"""Capacity-growth replays of the window's job."""


def read(run):
    return run["growth_replays"]
