"""nexmark-q7: the SQL, the plain reference, the control, least bytes."""
import numpy as np

import nexmark_ref as ref

MV = "nexmark_q7"
SOURCES = [ref.BID_SOURCE_SQL]
MV_SQL = """CREATE MATERIALIZED VIEW nexmark_q7 AS
SELECT B.auction, B.price, B.bidder, B.date_time
FROM bid B
JOIN (
    SELECT MAX(price) AS maxprice, window_end as date_time
    FROM TUMBLE(bid, date_time, INTERVAL '10' SECOND)
    GROUP BY window_end
) B1 ON B.price = B1.maxprice
WHERE B.date_time BETWEEN B1.date_time - INTERVAL '10' SECOND
      AND B1.date_time"""
READ_SQL = "SELECT * FROM nexmark_q7"
WINDOW_USECS = 10_000_000


def normalise(rows):
    """MV rows as read -> a list of int tuples (a multiset)."""
    return [(int(a), int(p), int(b), int(t)) for a, p, b, t in rows]


def _rows(cols):
    price, ts = cols["price"], cols["date_time"]
    wend = (ts // WINDOW_USECS) * WINDOW_USECS + WINDOW_USECS
    ends, (best,) = ref.groupby_reduce(wend, [("max", price)])
    rows = []
    for e, m in zip(ends, best):
        sel = (price == m) & (ts >= e - WINDOW_USECS) & (ts <= e)
        for i in np.flatnonzero(sel):
            rows.append((int(cols["auction"][i]), int(price[i]),
                         int(cols["bidder"][i]), int(ts[i])))
    return rows


def reference(seed, events):
    """The MV over events [0, events) of the seeded stream."""
    return _rows(ref.bid_columns(seed, ref.bid_event_ids(0, events)))


def control(seed, events, epoch_events):
    """The reference with the exactly-once guarantee broken: the last epoch
    of the stream is applied twice, as an at-least-once replay after a
    crash would."""
    ids = ref.replayed_bid_event_ids(events, epoch_events)
    return _rows(ref.bid_columns(seed, ids))


def counts(seed, events, epoch_events):
    """What the query has to touch, from the events alone."""
    bids = windows_touched = 0
    for lo in range(0, events, epoch_events):
        ids = ref.bid_event_ids(lo, min(events, lo + epoch_events))
        bids += len(ids)
        ts = ref.bid_columns(seed, ids, ("date_time",))["date_time"]
        windows_touched += len(np.unique(ts // WINDOW_USECS))
    return {"bids": bids, "windows_touched": windows_touched,
            "mv_changes": len(reference(seed, events)),
            "epochs": -(-events // epoch_events)}


def least_bytes(c):
    """Least bytes the query moves over the window: the four consumed
    columns once; every bid kept once on its join side (four columns);
    every touched window's key and maximum read and written once an epoch;
    every MV row (four values) written once. 8-byte values throughout."""
    return (c["bids"] * 4 * 8 + c["bids"] * 4 * 8
            + c["windows_touched"] * 2 * 8 * 2 + c["mv_changes"] * 4 * 8)
