"""Host milliseconds of the window's `rw:growth` spans: predict, resize and
replay after a state overflowed its capacity (0.0 when none did)."""
import spans


def read(run):
    p = spans.load()
    if p is None:
        return None
    return spans.seconds(p.of(p.window, "rw:growth")) * 1e3
