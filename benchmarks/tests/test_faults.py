"""The rest of a run with the timed path broken underneath: `correct` has
to come out false. Skips the look for a chip (`rehearse`), tiny sizes: each
case runs on the device-fed cell and on the host-fed one, whose rows the
stager generates and packs on the host."""
import pytest

import discover
import run

SEED = 2**31 + 11
CELLS = ["bid-agg.device", "bid-agg.hostfed28"]


def _run(workload):
    return run.run_cell(discover.Cell(workload), SEED, 30.0, trace=False,
                        rehearse=True)


def _epochs(workload):
    sz = run.sizes(discover.Cell(workload), True)
    return sz["events"] // sz["epoch_events"]


def _failed(result):
    return {k for k, v in result["compared"].items()
            if v["value"] > v["limit"]}


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(workload):
    result = _run(workload)
    assert result["correct"] and not _failed(result)
    assert result["rehearsal"] and result["metrics"] == {}
    assert result["counts"]["epochs"] == _epochs(workload)


@pytest.mark.parametrize("workload", CELLS)
def test_an_answer_altered_where_it_is_read(monkeypatch, workload):
    from risingwave_tpu.sql import Database
    query = Database.query

    def altered(self, sql):
        rows = query(self, sql)
        a, c, s, m = rows[0]
        return [(a, c, s + 1, m)] + list(rows[1:])
    monkeypatch.setattr(Database, "query", altered)
    result = _run(workload)
    assert not result["correct"]
    assert _failed(result) == {"rows_missing", "rows_unexpected"}


@pytest.mark.parametrize("workload", CELLS)
def test_an_answer_left_out(monkeypatch, workload):
    from risingwave_tpu.sql import Database
    query = Database.query
    monkeypatch.setattr(Database, "query",
                        lambda self, sql: list(query(self, sql))[1:])
    result = _run(workload)
    assert not result["correct"] and _failed(result) == {"rows_missing"}


def test_a_staged_row_altered(monkeypatch):
    """An answer altered where it is produced: the stager packs the third
    window of every job with one value of its first shipped column off by
    one."""
    from risingwave_tpu.device.ingest import HostIngest
    pack = HostIngest._pack_host

    def altered(self, lo, per_source):
        host = pack(self, lo, per_source)
        if lo == 2 * self.epoch_events:
            for _count, _pk, cols in host.values():
                cols[0][0] += 1
        return host
    monkeypatch.setattr(HostIngest, "_pack_host", altered)
    result = _run("bid-agg.hostfed28")
    assert not result["correct"]
    assert _failed(result) == {"rows_missing", "rows_unexpected"}


@pytest.mark.parametrize("workload", CELLS)
def test_an_epoch_counted_but_not_applied(monkeypatch, workload):
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
    result = _run(workload)
    assert not result["correct"] and "rows_missing" in _failed(result)


@pytest.mark.parametrize("workload", CELLS)
def test_a_barrier_that_a_recovery_replayed(monkeypatch, workload):
    """The `fused.dispatch` failpoint fires on the window's first dispatch:
    the set-up pass made one a rehearsal epoch."""
    from risingwave_tpu.device import fused
    real, seen, first = fused.failpoint, [], _epochs(workload) + 1

    def first_of_window(name):
        if name != "fused.dispatch":
            return real(name)
        seen.append(name)
        return len(seen) == first
    monkeypatch.setattr(fused, "failpoint", first_of_window)
    result = _run(workload)
    assert not result["correct"]
    assert {"recoveries", "barriers_replayed"} <= _failed(result)
    assert result["failed"] == 1
