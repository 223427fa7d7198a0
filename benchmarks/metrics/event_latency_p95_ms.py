"""95th percentile over every event of the window of: commit of the
checkpoint that made its epoch's result durable, minus the clock just
before the tick that admitted its epoch. Epochs weigh by their events."""
import window


def read(run):
    pairs = window.event_latencies(run["ticks"])
    return window.weighted_percentile(pairs, 0.95) * 1e3
