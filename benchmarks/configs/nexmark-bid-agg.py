"""nexmark-bid-agg: the SQL, the plain reference, the control, least bytes."""
import numpy as np

import nexmark_ref as ref

MV = "q4"
SOURCES = [ref.BID_SOURCE_SQL]
MV_SQL = ("CREATE MATERIALIZED VIEW q4 AS SELECT auction, count(*) AS c,"
          " sum(price) AS s, max(price) AS m FROM bid GROUP BY auction")
READ_SQL = "SELECT * FROM q4"


def normalise(rows):
    """MV rows as read -> a list of int tuples (a multiset)."""
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


def counts(seed, events, epoch_events):
    """What the query has to touch, from the events alone."""
    bids = groups_touched = 0
    for lo in range(0, events, epoch_events):
        ids = ref.bid_event_ids(lo, min(events, lo + epoch_events))
        bids += len(ids)
        groups_touched += len(np.unique(
            ref.bid_columns(seed, ids, ("auction",))["auction"]))
    return {"bids": bids, "groups_touched": groups_touched,
            "mv_changes": groups_touched,
            "epochs": -(-events // epoch_events)}


def least_bytes(c):
    """Least bytes the query moves over the window: the two consumed
    columns once; every touched group's key and three aggregates read and
    written once an epoch; every MV change (key and three values) written
    once. 8-byte values throughout."""
    return (c["bids"] * 2 * 8 + c["groups_touched"] * 4 * 8 * 2
            + c["mv_changes"] * 4 * 8)
