"""Host seconds the epoch loop spent enqueueing epoch programs (the job
profiler's `dispatch` phase) per epoch of the window."""


def read(run):
    if not run["epochs"] or "dispatch" not in run["phase_s"]:
        return None
    return run["phase_s"]["dispatch"] / run["epochs"] * 1e3
