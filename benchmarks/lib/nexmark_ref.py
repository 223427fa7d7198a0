"""Frozen plain reference: the NEXmark bid stream and a sort/reduce group-by.

numpy only; imports nothing of the program. Frozen copies (PR 25) of
`risingwave_tpu/connectors/nexmark.py` (`gen_surrogates`, bid table,
hot/cold picks) and `bench.py` (`groupby_reduce`), so that a later change to
the program cannot move the yardstick. Every column is a pure function of
(seed, event id): event n is a person if n % 50 == 0, an auction if n % 50 in
1..3, else a bid (1:3:46); bids pick a hot auction / bidder 90 % of the time
among the most recent 1/100 of the entities.
"""
import numpy as np

TOTAL_PROPORTION = 50
PERSON_PROPORTION = 1
AUCTION_PROPORTION = 3
FIRST_PERSON_ID = 1000
FIRST_AUCTION_ID = 1000
HOT_AUCTION_RATIO = 100
HOT_BIDDER_RATIO = 100
BASE_TIME_USECS = 1_500_000_000_000_000
INTER_EVENT_GAP_USECS = 100

# the bid source as RisingWave's e2e tests declare it (frozen from bench.py)
BID_SOURCE_SQL = (
    "CREATE SOURCE bid (auction BIGINT, bidder BIGINT, price BIGINT,"
    " channel VARCHAR, url VARCHAR, date_time TIMESTAMP, extra VARCHAR)"
    " WITH (connector='nexmark', nexmark.table='bid',"
    " nexmark.max.events='{events}', nexmark.chunk.size='{chunk}')")


def splitmix64(x):
    x = x + np.uint64(0x9E3779B97F4A7C15)
    with np.errstate(over="ignore"):
        z = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        z = z ^ (z >> np.uint64(31))
    return z


def _mulhi_bound(r, m):
    """Uniform u64 r -> [0, m): the high 64 bits of r*m."""
    mask, sh = np.uint64(0xFFFFFFFF), np.uint64(32)
    r, m = r.astype(np.uint64), m.astype(np.uint64)
    a0, a1, b0, b1 = r & mask, r >> sh, m & mask, m >> sh
    with np.errstate(over="ignore"):
        m00, m01, m10, m11 = a0 * b0, a0 * b1, a1 * b0, a1 * b1
        carry = (m00 >> sh) + (m01 & mask) + (m10 & mask)
        return (m11 + (m01 >> sh) + (m10 >> sh)
                + (carry >> sh)).astype(np.int64)


def _hot_pick(rand_hot, rand_pick, n_entities, hot_ratio):
    hot = (rand_hot % np.uint64(100)) < np.uint64(90)
    span = np.maximum(n_entities // hot_ratio, 1)
    return np.where(hot, n_entities - 1 - _mulhi_bound(rand_pick, span),
                    _mulhi_bound(rand_pick, n_entities))


def bid_event_ids(lo, hi):
    """Ids of the bid events among events [lo, hi)."""
    ids = np.arange(lo, hi, dtype=np.int64)
    return ids[ids % TOTAL_PROPORTION > AUCTION_PROPORTION]


def replayed_bid_event_ids(events, epoch_events):
    """Bid ids of events [0, events) with the last epoch applied twice: what
    an at-least-once replay after a crash would feed (the control that
    breaks the configurations' exactly-once guarantee)."""
    return np.concatenate([
        bid_event_ids(0, events),
        bid_event_ids(max(0, events - epoch_events), events)])


def bid_columns(seed, event_ids, cols=("auction", "bidder", "price",
                                       "date_time")):
    """{column: int64 array} of the bid table at these (bid) event ids."""
    base = np.uint64(int(seed) << 20)

    def rand(salt):
        with np.errstate(over="ignore"):
            return splitmix64(event_ids.astype(np.uint64)
                              + (base + np.uint64(salt)))

    full, rem = np.divmod(event_ids, TOTAL_PROPORTION)
    out = {}
    if "auction" in cols:
        n_auction = np.maximum(full * AUCTION_PROPORTION + np.clip(
            rem - PERSON_PROPORTION, 0, AUCTION_PROPORTION), 1)
        out["auction"] = (FIRST_AUCTION_ID + _hot_pick(
            rand(20), rand(21), n_auction, HOT_AUCTION_RATIO)
        ).astype(np.int64)
    if "bidder" in cols:
        n_person = np.maximum(full * PERSON_PROPORTION + (rem > 0), 1)
        out["bidder"] = (FIRST_PERSON_ID + _hot_pick(
            rand(22), rand(23), n_person, HOT_BIDDER_RATIO)
        ).astype(np.int64)
    if "price" in cols:
        out["price"] = 100 + (rand(24) % np.uint64(10_000)).astype(np.int64)
    if "date_time" in cols:
        out["date_time"] = (BASE_TIME_USECS
                            + event_ids * INTER_EVENT_GAP_USECS
                            ).astype(np.int64)
    return out


def groupby_reduce(keys, cols):
    """Sort + reduceat group-by: [(how, column), ...] -> (keys, results)."""
    order = np.argsort(keys, kind="stable")
    k = keys[order]
    bounds = np.flatnonzero(np.r_[True, k[1:] != k[:-1]])
    out = []
    for how, c in cols:
        if how == "count":
            out.append(np.diff(np.r_[bounds, len(k)]))
        elif how == "sum":
            out.append(np.add.reduceat(c[order], bounds))
        elif how == "max":
            out.append(np.maximum.reduceat(c[order], bounds))
        else:
            raise ValueError(f"unknown reduction {how!r}")
    return k[bounds], out
