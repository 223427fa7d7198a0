"""nexmark-q7-p4: the SQL, the plain reference, the control, least bytes.

NEXmark q7 at streaming parallelism 4: the query and the stream of
`nexmark-q7`, so the plain reference is the same arithmetic — its own copy,
numpy over `lib/nexmark_ref.py`, nothing of the program. What the four
shards add is in `counts` and `least_bytes`: the bid rows every chip ships
through the join's exchange.
"""
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

SHARDS = 4
# one bid row on the join's wire: auction, price, bidder, date_time
# (8 bytes each) and the int32 sign
EXCHANGE_ROW_BYTES = 4 * 8 + 4


def normalise(rows):
    """MV rows as read -> a list of int tuples (a multiset). A pair held on
    two shards would read back twice here and fail the multiset comparison
    (the configuration's partitioning guarantee)."""
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
    """What the query has to touch, from the events alone. Each chip makes
    a contiguous block of ceil(epoch / 4) events of an epoch and ships every
    bid it makes to the chip that owns the vnode of its price:
    `exchange_rows` counts those rows over the window, `exchange_rows_fullest`
    the same for the chip that ships most in each epoch."""
    bids = windows_touched = exchange_rows = exchange_rows_fullest = 0
    block = -(-epoch_events // SHARDS)
    for lo in range(0, events, epoch_events):
        hi = min(events, lo + epoch_events)
        ids = ref.bid_event_ids(lo, hi)
        bids += len(ids)
        ts = ref.bid_columns(seed, ids, ("date_time",))["date_time"]
        windows_touched += len(np.unique(ts // WINDOW_USECS))
        sent = [len(ref.bid_event_ids(lo + s * block,
                                      min(hi, lo + (s + 1) * block)))
                for s in range(SHARDS)]
        exchange_rows += sum(sent)
        exchange_rows_fullest += max(sent)
    return {"bids": bids, "windows_touched": windows_touched,
            "mv_changes": len(reference(seed, events)),
            "exchange_rows": exchange_rows,
            "exchange_rows_fullest": exchange_rows_fullest,
            "epochs": -(-events // epoch_events)}


def least_bytes(c):
    """Least bytes the FULLEST CHIP moves over the window, not the four
    chips together: `lib/trace.py` averages the module seconds over the
    chips that ran and `step_roofline` divides by ONE chip's 819 GB/s, so
    the bytes have to be one chip's too. A quarter of the query's least
    bytes (`nexmark-q7`: the four consumed columns once; every bid kept once
    on its join side; every touched window's key and maximum read and
    written once an epoch; every MV row written once; 8-byte values), plus
    the exchanged bid rows once out (the chip that ships most) and once in
    (a quarter of all that is shipped: CRC32 spreads the prices evenly over
    the four vnode blocks)."""
    query = (c["bids"] * 4 * 8 + c["bids"] * 4 * 8
             + c["windows_touched"] * 2 * 8 * 2 + c["mv_changes"] * 4 * 8)
    exchanged = c["exchange_rows_fullest"] + c["exchange_rows"] / SHARDS
    return query / SHARDS + exchanged * EXCHANGE_ROW_BYTES
