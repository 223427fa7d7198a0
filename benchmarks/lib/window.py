"""The window loop and the arithmetic of its two end-to-end metrics.

`drive` is the one pass both set-up (untimed) and the window (timed) make:
the served SQL path in a closed loop, one `Database.tick()` per barrier.
"""
import time


def drive(db, job, seconds, annotate, clock=time.perf_counter):
    """Tick until the bounded stream is drained or `seconds` have passed,
    then until the checkpoint that covers the last epoch has committed, then
    `sync()`. Returns the timeline: one record per barrier with the clock
    just before the tick, just after it, the events it admitted, the
    job's committed event count after it and the in-place recoveries that
    replayed it. A tick that raises ends the run."""
    ticks = []
    t0 = clock()

    def tick(label):
        before, rec0 = job.counter, job.recoveries
        tb = clock()
        with annotate(label):
            db.tick()
        ticks.append({"label": label, "t_admit": tb - t0,
                      "t_done": clock() - t0,
                      "events": job.counter - before,
                      "committed": job.committed,
                      "recovered": job.recoveries - rec0})

    k = 0
    while job.counter < job.max_events and clock() - t0 < seconds:
        tick(f"tick:{k}")
        k += 1
    while job.committed < job.counter:
        tick(f"tick:{k}:drain")
        k += 1
    with annotate("sync"):
        job.sync()
    return {"window_s": clock() - t0, "ticks": ticks,
            "events_committed": job.committed}


def event_latencies(ticks):
    """[(latency_s, events)] per epoch: from just before the tick that
    admitted the epoch to the return of the tick whose checkpoint made it
    durable (the first after which `committed` covers it)."""
    out, admitted = [], 0
    for i, t in enumerate(ticks):
        if t["events"] <= 0:
            continue
        admitted += t["events"]
        done = next((u["t_done"] for u in ticks[i:]
                     if u["committed"] >= admitted), None)
        if done is None:
            raise ValueError(f"epoch of {t['label']} never committed")
        out.append((done - t["t_admit"], t["events"]))
    return out


def weighted_percentile(pairs, q):
    """The smallest value v with at least `q` of the weight at or under v."""
    pairs = sorted(pairs)
    total = sum(w for _, w in pairs)
    if total <= 0:
        raise ValueError("no weight")
    acc = 0
    for v, w in pairs:
        acc += w
        if acc >= q * total:
            return v
    return pairs[-1][0]
