"""Host milliseconds of the `rw:commit.mirror.decode` spans (the MV's
VARCHAR columns turned from surrogates into strings) under the window's
`rw:commit.mirror.pull` spans, per checkpoint that committed in the window.
The same span under a SELECT's pull is not the commit's and is left out."""
import spans


def read(run):
    p = spans.load()
    if p is None or not run["checkpoints"]:
        return None
    pulls = {s["id"] for s in p.of(p.window, "rw:commit.mirror.pull")}
    decode = [s for s in p.of(p.window, "rw:commit.mirror.decode")
              if s["parent"] in pulls]
    if not decode:
        return None
    return spans.seconds(decode) / run["checkpoints"] * 1e3
