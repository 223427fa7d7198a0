"""`q7.mesh4` by hand on four CPU devices: the rehearsal is `correct` and
prints counts only, an answer in which a join pair is held on two shards
comes out not correct (the configuration's partitioning guarantee), and
the control — the last epoch applied twice — differs from the reference
at the cell's own size.

Needs four devices: `test_mesh4` asks for them before jax starts; where
jax came up with fewer, the tests skip and say so."""
import pytest

import discover
import run
from test_mesh4 import _failed, four_devices  # noqa: F401  (fixture)

SEED = 2**31 + 31
CELL = "q7.mesh4"


def test_rehearsal_is_correct_and_prints_counts_only(four_devices):
    result = run.run_cell(discover.Cell(CELL), SEED, 30.0, trace=True,
                          rehearse=True)
    assert result["correct"] and not _failed(result)
    assert result["rehearsal"] and result["metrics"] == {}
    assert result["device"] == {"platform": "cpu", "kind": "cpu", "count": 4}
    # every shard's join side outgrows its 1,024 slots once
    assert result["counts"] == {"events_committed": 8192, "epochs": 8,
                                "checkpoints": 2, "growth_replays": 1,
                                "window_compiles": 0}
    assert {"growth_replays", "growth_replay_ms"} \
        <= set(result["metric_names"])


def test_a_pair_held_on_two_shards(four_devices, monkeypatch):
    """A pair the pair MV held on two shards reads back twice: the
    comparison counts the second copy as unexpected."""
    cell = discover.Cell(CELL)
    once = cell.config_code.normalise
    monkeypatch.setattr(cell.config_code, "normalise",
                        lambda rows: once(rows) + once(rows)[:1])
    result = run.run_cell(cell, SEED, 30.0, trace=False, rehearse=True)
    assert not result["correct"]
    assert _failed(result) == {"rows_unexpected"}
    assert result["compared"]["rows_unexpected"]["value"] == 1


@pytest.mark.parametrize("seed", [1, 42, 2**31 + 7])
def test_control_fails_at_the_cells_size(seed):
    """The cell's stream: 1,048,576 events in epochs of 131,072."""
    code = discover.Cell(CELL).config_code
    events, epoch = 1_048_576, 131_072
    want = code.reference(seed, events)
    assert run.multiset_diff(code.reference(seed, events), want) == (0, 0)
    missing, unexpected = run.multiset_diff(
        code.control(seed, events, epoch), want)
    assert missing + unexpected > 0
