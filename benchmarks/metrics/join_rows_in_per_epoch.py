"""Live rows of both inputs' change sets that reached the join step, per
epoch of the window."""
import flow


def read(run):
    joins = flow.nodes("JoinNode")
    if not joins or not run["epochs"]:
        return None
    return sum(n["rows_in"] for n in joins) / run["epochs"]
