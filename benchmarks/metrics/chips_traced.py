"""Device planes of the trace on which an operation ran: reads 4, or the
cell did not run where it says."""


def read(run):
    tr = run.get("trace")
    return tr["chips_traced"] if tr else None
