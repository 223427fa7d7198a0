"""How far the fullest shard's live groups at the drain lie above the mean
over the shards, for the keyed node that holds most: 0 is an even split."""
import shards


def read(run):
    rep = shards.report()
    if not rep or not rep["keyed"]:
        return None
    live = max((k["live"] for k in rep["keyed"]), key=sum)
    if not sum(live):
        return None
    return 100.0 * (max(live) * len(live) / sum(live) - 1.0)
