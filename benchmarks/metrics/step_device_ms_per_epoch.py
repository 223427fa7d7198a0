"""Device seconds of the XLA modules run in the traced window, per epoch
dispatched in it (growth replays included)."""


def read(run):
    tr = run.get("trace")
    if not tr or not run["epochs"] or not tr["modules_s"]:
        return None
    return tr["modules_s"] / run["epochs"] * 1e3
