"""Host seconds of the job profiler's `commit` phase per checkpoint that
committed in the window."""


def read(run):
    if not run["checkpoints"] or "commit" not in run["phase_s"]:
        return None
    return run["phase_s"]["commit"] / run["checkpoints"] * 1e3
