"""The rest of a run with the timed path broken underneath: `correct` has
to come out false. Skips the look for a chip (`rehearse`), tiny sizes."""
import pytest

import discover
import run

SEED = 2**31 + 11


def _run(workload="bid-agg.device"):
    return run.run_cell(discover.Cell(workload), SEED, 30.0, trace=False,
                        rehearse=True)


def _failed(result):
    return {k for k, v in result["compared"].items()
            if v["value"] > v["limit"]}


def test_sound_run_is_correct():
    result = _run()
    assert result["correct"] and not _failed(result)
    assert result["rehearsal"] and result["metrics"] == {}


def test_an_answer_altered_where_it_is_read(monkeypatch):
    from risingwave_tpu.sql import Database
    query = Database.query

    def altered(self, sql):
        rows = query(self, sql)
        a, c, s, m = rows[0]
        return [(a, c, s + 1, m)] + list(rows[1:])
    monkeypatch.setattr(Database, "query", altered)
    result = _run()
    assert not result["correct"]
    assert _failed(result) == {"rows_missing", "rows_unexpected"}


def test_an_answer_left_out(monkeypatch):
    from risingwave_tpu.sql import Database
    query = Database.query
    monkeypatch.setattr(Database, "query",
                        lambda self, sql: list(query(self, sql))[1:])
    result = _run()
    assert not result["correct"] and _failed(result) == {"rows_missing"}


def test_an_epoch_counted_but_not_applied(monkeypatch):
    """The part of the batch that is left out: the third epoch of every job
    moves the event counter and never reaches the device."""
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
    result = _run()
    assert not result["correct"] and "rows_missing" in _failed(result)


def test_a_barrier_that_a_recovery_replayed(monkeypatch):
    """The `fused.dispatch` failpoint fires on the ninth dispatch of the
    process: the set-up pass made eight, so it is the window's first."""
    from risingwave_tpu.device import fused
    real, seen = fused.failpoint, []

    def ninth(name):
        if name != "fused.dispatch":
            return real(name)
        seen.append(name)
        return len(seen) == 9
    monkeypatch.setattr(fused, "failpoint", ninth)
    result = _run()
    assert not result["correct"]
    assert {"recoveries", "barriers_replayed"} <= _failed(result)
    assert result["failed"] == 1
