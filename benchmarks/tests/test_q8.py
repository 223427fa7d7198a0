"""`q8.device` by hand on the CPU: the rehearsal is `correct`, prints counts
only and its four own per-layer metrics find something to read; a run whose
timed path loses an epoch comes out not correct (the half of exactly-once
this configuration's `correct` can show)."""
import discover
import run

SEED = 2**31 + 41
CELL = "q8.device"
OWN = ["source_fill_pct", "join_rows_in_per_epoch", "state_fill_pct",
       "mirror_decode_ms_per_ckpt"]


def _failed(result):
    return {k for k, v in result["compared"].items()
            if v["value"] > v["limit"]}


def test_rehearsal_is_correct_and_prints_counts_only():
    cell = discover.Cell(CELL)
    assert [m["name"] for m, _ in cell.metrics("per_layer")
            if "workloads" in m] == OWN
    result = run.run_cell(cell, SEED, 30.0, trace=True, rehearse=True)
    assert result["correct"] and not _failed(result)
    assert result["rehearsal"] and result["metrics"] == {}
    assert result["device"]["platform"] == "cpu"
    # 40 epochs of 8,192 events: 3.28 tumbling windows of 100,000
    assert result["counts"] == {"events_committed": 327680, "epochs": 40,
                                "checkpoints": 6, "growth_replays": 0,
                                "window_compiles": 0}
    assert set(OWN) <= set(result["metric_names"])


def test_an_epoch_counted_but_not_applied(monkeypatch):
    """The third epoch of every job moves the event counter and never
    reaches the device: its persons and its auctions are lost, rows are
    missing and none is unexpected. (The same epoch applied twice would
    read `correct`: see the configuration's `replayed`.)"""
    from risingwave_tpu.device.fused import FusedJob
    dispatch = FusedJob._dispatch_epoch

    def skipping(self, prof):
        n = self.__dict__.setdefault("_bench_epochs", 0)
        self._bench_epochs = n + 1
        if n != 2:
            return dispatch(self, prof)
        events = self.program.epoch_events
        self._epoch_log.append(self.counter, events)
        self.counter += events
        return True
    monkeypatch.setattr(FusedJob, "_dispatch_epoch", skipping)
    result = run.run_cell(discover.Cell(CELL), SEED, 30.0, trace=False,
                          rehearse=True)
    assert not result["correct"] and _failed(result) == {"rows_missing"}
