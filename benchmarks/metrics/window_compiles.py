"""Programs compiled, failed to compile or stepped inline inside the
window (compile service deltas). Expected 0: set-up warms every shape."""


def read(run):
    return run["window_compiles"]
