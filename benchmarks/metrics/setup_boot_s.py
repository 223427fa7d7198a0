"""Seconds from the start of the process (the OS's, `rw:boot`'s start) to
the start of the first SQL statement (`rw:sql`): interpreter, imports, the
backend's start, whatever the caller did before its first CREATE."""
import setup_spans
import spans


def read(run):
    p = spans.load()
    boot = setup_spans.boot(p)
    sql = spans.named(p.spans, "rw:sql") if boot is not None else []
    if not sql:
        return None
    return (sql[0]["t0"] - boot["t0"]) / 1e9
