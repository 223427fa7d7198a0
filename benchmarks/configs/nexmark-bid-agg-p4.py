"""nexmark-bid-agg-p4: the SQL, the plain reference, the control, least bytes.

The bid group-by of `nexmark-bid-agg` at streaming parallelism 4: the same
query over the same stream, so the plain reference is the same arithmetic —
its own copy, numpy over `lib/nexmark_ref.py`, nothing of the program. What
the four shards add is in `counts` and `least_bytes`: the pre-combined rows
every chip ships through the exchange.
"""
import numpy as np

import nexmark_ref as ref

MV = "q4"
SOURCES = [ref.BID_SOURCE_SQL]
MV_SQL = ("CREATE MATERIALIZED VIEW q4 AS SELECT auction, count(*) AS c,"
          " sum(price) AS s, max(price) AS m FROM bid GROUP BY auction")
READ_SQL = "SELECT * FROM q4"

SHARDS = 4
# one pre-combined row on the wire: key, raw-row count, sum and max
# partials (8 bytes each) and the int32 sign
EXCHANGE_ROW_BYTES = 4 * 8 + 4


def normalise(rows):
    """MV rows as read -> a list of int tuples (a multiset). A group held
    on two shards would read back as two rows here and fail the multiset
    comparison (the configuration's partitioning guarantee)."""
    return [(int(a), int(c), int(s), int(m)) for a, c, s, m in rows]


def _rows(cols):
    keys, (c, s, m) = ref.groupby_reduce(
        cols["auction"], [("count", None), ("sum", cols["price"]),
                          ("max", cols["price"])])
    return list(zip(keys.tolist(), c.tolist(), s.tolist(), m.tolist()))


def reference(seed, events):
    """The MV over events [0, events) of the seeded stream."""
    ids = ref.bid_event_ids(0, events)
    return _rows(ref.bid_columns(seed, ids, ("auction", "price")))


def control(seed, events, epoch_events):
    """The reference with the exactly-once guarantee broken: the last epoch
    of the stream is applied twice, as an at-least-once replay after a
    crash would."""
    ids = ref.replayed_bid_event_ids(events, epoch_events)
    return _rows(ref.bid_columns(seed, ids, ("auction", "price")))


def _uniques(seed, lo, hi):
    if hi <= lo:
        return 0
    ids = ref.bid_event_ids(lo, hi)
    return len(np.unique(ref.bid_columns(seed, ids, ("auction",))["auction"]))


def counts(seed, events, epoch_events):
    """What the query has to touch, from the events alone. Each chip makes
    a contiguous block of ceil(epoch / 4) events of an epoch and pre-combines
    it to one row a group before the exchange: `exchange_rows` counts those
    rows over the window, `exchange_rows_fullest` the same for the chip that
    ships most in each epoch."""
    bids = groups_touched = exchange_rows = exchange_rows_fullest = 0
    block = -(-epoch_events // SHARDS)
    for lo in range(0, events, epoch_events):
        hi = min(events, lo + epoch_events)
        bids += len(ref.bid_event_ids(lo, hi))
        groups_touched += _uniques(seed, lo, hi)
        sent = [_uniques(seed, lo + s * block, min(hi, lo + (s + 1) * block))
                for s in range(SHARDS)]
        exchange_rows += sum(sent)
        exchange_rows_fullest += max(sent)
    return {"bids": bids, "groups_touched": groups_touched,
            "mv_changes": groups_touched, "exchange_rows": exchange_rows,
            "exchange_rows_fullest": exchange_rows_fullest,
            "epochs": -(-events // epoch_events)}


def least_bytes(c):
    """Least bytes the FULLEST CHIP moves over the window, not the four
    chips together: `lib/trace.py` averages the module seconds over the
    chips that ran and `step_roofline` divides by ONE chip's 819 GB/s, so
    the bytes have to be one chip's too. A quarter of the query's least
    bytes (`nexmark-bid-agg`: the two consumed columns once; every touched
    group's key and three aggregates read and written once an epoch; every
    MV change written once; 8-byte values), plus the exchanged pre-combined
    rows once out (the chip that ships most) and once in (a quarter of all
    that is shipped: CRC32 spreads the keys evenly over the four vnode
    blocks)."""
    query = (c["bids"] * 2 * 8 + c["groups_touched"] * 4 * 8 * 2
             + c["mv_changes"] * 4 * 8)
    exchanged = c["exchange_rows_fullest"] + c["exchange_rows"] / SHARDS
    return query / SHARDS + exchanged * EXCHANGE_ROW_BYTES
