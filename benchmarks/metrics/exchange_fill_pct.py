"""Live rows the fullest shard received from the exchange over the slots the
exchange handed its step (`shards x exch` a stage and epoch), mean over the
window's epochs: the rest of the merge's input is padding."""
import shards


def read(run):
    rep = shards.report()
    if not rep or not rep["exchanges"] or not run["epochs"]:
        return None
    rows = sum(max(x["rows_in"]) for x in rep["exchanges"])
    slots = sum(x["slots"] for x in rep["exchanges"]) * run["epochs"]
    return 100.0 * rows / slots if slots else None
