"""Host seconds the stager spent generating and packing a window's rows
into its staging buffers, per staged window (`HostIngest.stats()`)."""


def read(run):
    st = run.get("ingest")
    if not st or not st.get("windows"):
        return None
    return st["pack_s"] / st["windows"] * 1e3
