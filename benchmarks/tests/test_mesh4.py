"""`bid-agg.mesh4` by hand on four CPU devices: the rehearsal is `correct`
and prints counts only, and an answer in which groups are split over two
shards comes out not correct (the configuration's partitioning guarantee).

Needs four devices: this file asks for them before jax starts
(`XLA_FLAGS=--xla_force_host_platform_device_count=4`); where jax came up
with fewer, the tests skip and say so."""
import os
import zlib

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=4")

import numpy as np
import pytest

import discover
import nexmark_ref as ref
import run

SEED = 2**31 + 29
CELL = "bid-agg.mesh4"


@pytest.fixture(scope="module")
def four_devices():
    import jax
    if len(jax.devices()) < 4:
        pytest.skip("needs XLA_FLAGS=--xla_force_host_platform_device_"
                    f"count=4 before jax starts; have {len(jax.devices())}")


def _failed(result):
    return {k for k, v in result["compared"].items()
            if v["value"] > v["limit"]}


def test_rehearsal_is_correct_and_prints_counts_only(four_devices):
    result = run.run_cell(discover.Cell(CELL), SEED, 30.0, trace=True,
                          rehearse=True)
    assert result["correct"] and not _failed(result)
    assert result["rehearsal"] and result["metrics"] == {}
    assert result["device"] == {"platform": "cpu", "kind": "cpu", "count": 4}
    assert result["counts"] == {"events_committed": 65536, "epochs": 8,
                                "checkpoints": 2, "growth_replays": 0,
                                "window_compiles": 0}
    # the cell's own per-layer metrics found something to read
    assert {"exchange_ms_per_epoch", "exchange_fill_pct", "shard_spread_pct",
            "rebalances"} <= set(result["metric_names"])


def _first_block(auctions):
    """Auctions whose id hashes (CRC32 of its 8 big-endian bytes) into the
    first of four contiguous blocks of 64 of the 256 virtual nodes."""
    vn = np.array([zlib.crc32(int(a).to_bytes(8, "big", signed=True)) % 256
                   for a in np.unique(auctions)])
    return np.unique(auctions)[vn < 64]


def split_reference(seed, events):
    """The reference as a job would answer that held the groups of one
    vnode block on TWO shards: each such group reads back as two partial
    rows (its odd and its even events), not as one."""
    ids = ref.bid_event_ids(0, events)
    cols = ref.bid_columns(seed, ids, ("auction", "price"))
    held_twice = np.isin(cols["auction"], _first_block(cols["auction"]))
    part = cols["auction"] * 2 + np.where(held_twice, ids % 2, 0)
    keys, (c, s, m) = ref.groupby_reduce(
        part, [("count", None), ("sum", cols["price"]),
               ("max", cols["price"])])
    return list(zip((keys // 2).tolist(), c.tolist(), s.tolist(),
                    m.tolist()))


def test_a_group_held_on_two_shards(four_devices, monkeypatch):
    cell = discover.Cell(CELL)
    whole = cell.config_code.reference(SEED, 65536)
    split = split_reference(SEED, 65536)
    assert len(split) > len(whole)          # some groups came out as two
    assert sorted({r[0] for r in split}) == sorted(r[0] for r in whole)
    monkeypatch.setattr(cell.config_code, "reference", split_reference)
    result = run.run_cell(cell, SEED, 30.0, trace=False, rehearse=True)
    assert not result["correct"]
    assert _failed(result) == {"rows_missing", "rows_unexpected"}
