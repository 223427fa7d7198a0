"""Bytes of the staging buffers shipped to the device over the seconds the
stager waited for those transfers (`HostIngest.stats()` `h2d_s`)."""


def read(run):
    st = run.get("ingest")
    if not st or not st.get("h2d_s") or not st.get("feed_bytes"):
        return None
    return st["feed_bytes"] * st["windows"] / st["h2d_s"] / 1e6
