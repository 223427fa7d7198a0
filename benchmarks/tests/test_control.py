"""The control of `correct`: the reference with the configuration's
exactly-once guarantee broken (the last epoch applied twice) has to come out
as not correct, and the reference against itself as correct."""
import pytest

import discover
import run

CELLS = [("bid-agg.device", 65_536, 8_192), ("q7.device", 262_144, 32_768)]


@pytest.mark.parametrize("workload,events,epoch", CELLS)
@pytest.mark.parametrize("seed", [1, 42, 2**31 + 7])
def test_control_fails_and_reference_passes(workload, events, epoch, seed):
    code = discover.Cell(workload).config_code
    want = code.reference(seed, events)
    assert run.multiset_diff(code.reference(seed, events), want) == (0, 0)
    missing, unexpected = run.multiset_diff(
        code.control(seed, events, epoch), want)
    assert missing + unexpected > 0
