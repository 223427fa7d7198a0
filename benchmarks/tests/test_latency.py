"""The event-weighted latency tail on hand-made timelines."""
import pytest

import window


def tick(label, t_admit, t_done, events, committed):
    return {"label": label, "t_admit": t_admit, "t_done": t_done,
            "events": events, "committed": committed, "recovered": 0}


def test_epochs_wait_for_the_checkpoint_that_covers_them():
    # the first barrier commits at once; the next three wait for the drain
    ticks = [tick("tick:0", 0.0, 1.0, 100, 100),
             tick("tick:1", 1.0, 2.0, 100, 100),
             tick("tick:2", 2.0, 3.0, 100, 100),
             tick("tick:3", 3.0, 4.0, 100, 100),
             tick("tick:4:drain", 4.0, 4.5, 0, 400)]
    assert window.event_latencies(ticks) == [(1.0, 100), (3.5, 100),
                                             (2.5, 100), (1.5, 100)]
    # 95 % of 400 events = 380: only the slowest epoch reaches it
    assert window.weighted_percentile(window.event_latencies(ticks),
                                      0.95) == 3.5
    assert window.weighted_percentile(window.event_latencies(ticks),
                                      0.5) == 1.5


def test_a_stall_moves_the_tail_and_weights_are_events():
    # every barrier a checkpoint; one 10 s stall on an epoch of 5 % of the
    # events is exactly at the 95th percentile's edge, 6 % is beyond it
    def timeline(stalled_events):
        rest = (1000 - stalled_events) // 3
        out, t, done = [], 0.0, 0
        for k, (ev, dt) in enumerate([(rest, 1.0), (stalled_events, 10.0),
                                      (rest, 1.0), (rest, 1.0)]):
            done += ev
            out.append(tick(f"tick:{k}", t, t + dt, ev, done))
            t += dt
        return out
    p95 = lambda ticks: window.weighted_percentile(
        window.event_latencies(ticks), 0.95)
    assert p95(timeline(40)) == 1.0
    assert p95(timeline(70)) == 10.0


def test_an_epoch_that_never_commits_is_an_error():
    ticks = [tick("tick:0", 0.0, 1.0, 100, 0)]
    with pytest.raises(ValueError):
        window.event_latencies(ticks)
