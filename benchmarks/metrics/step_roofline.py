"""Least time the chip's memory could take for the bytes the QUERY has to
move in the window (the configuration's `least_bytes`, from the events) over
the device seconds of the XLA modules. Memory-bound: bytes over HBM bytes/s."""


def read(run):
    tr = run.get("trace")
    if not tr or not tr["modules_s"] or not run.get("least_bytes"):
        return None
    least_s = run["least_bytes"] / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / tr["modules_s"]
