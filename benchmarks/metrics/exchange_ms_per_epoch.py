"""Host milliseconds of the `rw:exchange` spans of the window's job (the
enqueue of the in-program shuffle, one span an exchange stage) per epoch;
the shuffle's device time is in the device trace, not here."""
import spans


def read(run):
    p = spans.load()
    if p is None or not run["epochs"]:
        return None
    exchange = p.of(p.window, "rw:exchange")
    if not exchange:
        return None
    return spans.seconds(exchange) / run["epochs"] * 1e3
