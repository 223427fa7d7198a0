"""Share of the window the host spent blocked on the device (the job
profiler's `device_sync` phase). Host time, not a device metric."""


def read(run):
    if "device_sync" not in run["phase_s"]:
        return None
    return 100.0 * run["phase_s"]["device_sync"] / run["window_s"]
