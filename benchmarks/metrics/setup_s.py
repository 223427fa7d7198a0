"""Start of the process to the start of the window: imports, CREATE, one
untimed pass of the same stream (every compile or cache load), idle wait."""


def read(run):
    return run["setup_s"]
