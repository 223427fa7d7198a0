"""Peak bytes in use on the fullest chip after the window
(`device.memory_stats()`): scratch pass and window together."""


def read(run):
    if not run.get("memory_peak_bytes"):
        return None
    return run["memory_peak_bytes"] / 1e6
