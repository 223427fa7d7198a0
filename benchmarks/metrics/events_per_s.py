"""Source events whose result is in the committed MV at the end of the
window, over the whole window's wall seconds (first tick to last commit)."""


def read(run):
    return run["events_committed"] / run["window_s"]
