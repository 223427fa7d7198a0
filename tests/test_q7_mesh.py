"""NEXmark q7 at `mesh_shards=4` with the benchmark's defaults: the
deployment the cell `q7.mesh4` measures on four chips, at a small size on
four of the CPU's virtual devices.

Both join inputs cross the in-program exchange, both join sides are
sharded by the vnode of `price`, the tumble agg by the vnode of
`window_end`. Tier-1 pins the default-on traced features off (conftest);
the benchmark runs the defaults, so this file forces them back on. The
yardstick is the benchmark's frozen numpy reference
(`benchmarks/lib/nexmark_ref.py`) through the configuration's own
`reference` and `counts` (`benchmarks/configs/nexmark-q7-p4.py`), neither
of which imports the program.
"""
import collections
import importlib.util
import os
import sys
import zlib

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the configuration's .py imports `nexmark_ref` from the benchmark's lib
sys.path.insert(0, os.path.join(ROOT, "benchmarks", "lib"))

import nexmark_ref as ref  # noqa: E402
from risingwave_tpu.config import DeviceConfig  # noqa: E402
from risingwave_tpu.core.vnode import VNODE_COUNT  # noqa: E402
from risingwave_tpu.parallel.mesh import shard_of_vnode  # noqa: E402
from risingwave_tpu.sql import Database  # noqa: E402

pytestmark = pytest.mark.mesh

SHARDS = 4
ARMED = ("RW_SKEW_STATS", "RW_FLOW_STATS", "RW_AGG_PRECOMBINE",
         "RW_STATE_TIERING")
SEED = 2**31 + 11
# four epochs of 64 polls x 512 events: two 10 s windows, ~120 k bids
CHUNK, POLLS, EVENTS = 512, 64, 131072
# a shard's join side holds its ~30 k bids without a growth
CAPACITY = 65536


def _config_code():
    """The configuration's .py, loaded as the benchmark's runner loads it."""
    path = os.path.join(ROOT, "benchmarks", "configs", "nexmark-q7-p4.py")
    spec = importlib.util.spec_from_file_location("bench_config_q7p4", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


CODE = _config_code()


@pytest.fixture(scope="module")
def armed():
    """The benchmark's configuration: every default-on traced feature on."""
    mp = pytest.MonkeyPatch()
    for k in ARMED:
        mp.setenv(k, "1")
    yield mp
    mp.undo()


_RUNS = {}


def _drive(armed, shards, capacity, exch=None):
    """One drained run of the cell's statements (cached per module), with
    the first exchange bucket forced to `exch` rows where given:
    (MV rows as read, job)."""
    key = (shards, capacity, exch)
    if key in _RUNS:
        return _RUNS[key]
    from risingwave_tpu.connectors.nexmark import (NexmarkConfig,
                                                   NexmarkGenerator)
    from risingwave_tpu.device import capacity as cap_mod
    from risingwave_tpu.device import fuse_planner
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(fuse_planner, "EPOCH_POLLS", POLLS)
        if exch is not None:
            mp.setattr(cap_mod, "exchange_cap",
                       lambda epoch_events, n_shards, lo=exch: exch)
        db = Database(device=DeviceConfig(capacity=capacity,
                                          mesh_shards=shards,
                                          mv_persist_every=64),
                      checkpoint_frequency=8)
        db._nexmark_gen = NexmarkGenerator(NexmarkConfig(seed=SEED))
        for sql in CODE.SOURCES:
            db.run(sql.format(events=EVENTS, chunk=CHUNK))
        db.run(CODE.MV_SQL)
    job = db.catalog.get(CODE.MV).runtime["fused_job"]
    assert job is not None and job.program.epoch_events == CHUNK * POLLS
    while job.counter < job.max_events or job.committed < job.counter:
        db.tick()
    job.sync()
    assert job.counter == job.committed == EVENTS and job.recoveries == 0
    _RUNS[key] = db.query(CODE.READ_SQL), job
    return _RUNS[key]


def _node(job, kind):
    return next(i for i, n in enumerate(job.program.nodes)
                if type(n).__name__ == kind)


def _price_shards(prices, pack):
    """The shard that owns the vnode of each price's packed join key (the
    join's one-field `PackPlan`: `(price - offset) // stride`): CRC32 of
    the key's 8 big-endian bytes, as the host-side `compute_vnodes` has it
    for one int64 key."""
    (field,) = pack.fields
    keys = (prices - field.offset) // field.stride
    vn = np.array([zlib.crc32(int(k).to_bytes(8, "big", signed=True))
                   % VNODE_COUNT for k in keys], dtype=np.int64)
    return shard_of_vnode(vn, SHARDS, VNODE_COUNT)


def _check_shares(job):
    """Every bid crossed the join's exchange to the shard that owns the
    vnode of its price and sits on that shard's join side; the tumble
    agg holds one entry a window; nothing was rebalanced."""
    cols = ref.bid_columns(SEED, ref.bid_event_ids(0, EVENTS),
                           ("price", "date_time"))
    windows = len(np.unique(cols["date_time"] // CODE.WINDOW_USECS))
    counts = CODE.counts(SEED, EVENTS, CHUNK * POLLS)
    join, agg = _node(job, "JoinNode"), _node(job, "AggNode")
    per_shard = np.bincount(
        _price_shards(cols["price"], job.program.nodes[join].pack),
        minlength=SHARDS).tolist()
    report = job.shard_report()
    assert report["shards"] == SHARDS and report["rebalances"] == 0
    stages = {(x["i"], x["xi"]): x["rows_in"] for x in report["exchanges"]}
    assert set(stages) == {(agg, 0), (join, 0), (join, 1)}
    assert stages[(join, 0)] == per_shard
    assert sum(stages[(join, 0)]) == counts["exchange_rows"] \
        == counts["bids"]
    live = {k["i"]: k["live"] for k in report["keyed"]}
    # a shard's join live = its bids (side a) + the window maxima it holds
    assert sum(live[join]) == counts["bids"] + windows
    assert all(j >= b for j, b in zip(live[join], per_shard))
    assert sum(live[agg]) == windows


CASES = ["reference", "one_chip", "growth", "exchange_overflow"]


@pytest.mark.parametrize("case", CASES)
def test_q7_at_four_shards(armed, case):
    """(reference) the MV at four shards equals the plain numpy q7 as a
    multiset, and the shard report adds up to the reference's counts;
    (one_chip) it is the `mesh_shards=1` run's MV, row for row and in
    order; (growth) at 1,024 slots a shard the join side overflows, grows
    through the sharded replay and still answers the same; (exchange_
    overflow) a first send bucket of 4 rows overflows the exchange's
    `exch` stat, which grows and replays to the same answer."""
    want = collections.Counter(CODE.reference(SEED, EVENTS))
    assert sum(want.values()) > 2
    rows, job = _drive(armed, SHARDS, CAPACITY)
    assert job.program.mesh is not None \
        and job.program.mesh.devices.size == SHARDS
    join = _node(job, "JoinNode")
    if case == "reference":
        assert job.growth_replays == 0
        assert collections.Counter(CODE.normalise(rows)) == want
        _check_shares(job)
    elif case == "one_chip":
        one, j1 = _drive(armed, 1, SHARDS * CAPACITY)
        assert j1.program.mesh is None and j1.growth_replays == 0
        assert rows == one
    elif case == "growth":
        grown, jg = _drive(armed, SHARDS, 1024)
        assert jg.growth_replays >= 1
        assert jg.program.nodes[join].cap_current()["a"] > 1024
        assert collections.Counter(CODE.normalise(grown)) == want
        assert grown == rows
    else:
        grown, jx = _drive(armed, SHARDS, CAPACITY, exch=4)
        assert jx.growth_replays >= 1
        assert jx.program.nodes[join].exch > 4
        assert collections.Counter(CODE.normalise(grown)) == want
        assert grown == rows
