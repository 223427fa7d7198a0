"""The longest `Database.tick()` of the window, by the runner's clock."""


def read(run):
    if not run["ticks"]:
        return None
    return max(t["t_done"] - t["t_admit"] for t in run["ticks"]) * 1e3
