"""Frozen plain reference: the NEXmark person and auction streams.

numpy only; imports `nexmark_ref` (the frozen bid stream, for `splitmix64`,
`_mulhi_bound` and the constants) and nothing of the program. Frozen copies
(PR 32) of the person and auction columns NEXmark q8 reads, out of
`risingwave_tpu/connectors/nexmark.py` (`gen_persons`, `gen_auctions`,
`gen_surrogates`, the `FIRST_NAMES` x `LAST_NAMES` name pool), and of the two
source DDLs out of `bench.py`, so that a later change to the program cannot
move the yardstick. Every column is a pure function of (seed, event id):
event n is a person if n % 50 == 0 and an auction if n % 50 in 1..3; ids are
dense per entity; a person's name is drawn on its id; an auction's seller is,
nine times in ten, one of the most recent 1/100 of the persons.
"""
import numpy as np

import nexmark_ref as ref

HOT_SELLER_RATIO = 100
FIRST_NAMES = ["peter", "paul", "luke", "john", "saul", "vicky", "kate",
               "julie", "sarah", "deiter", "walter"]
LAST_NAMES = ["shultz", "abrams", "spencer", "white", "bartels", "walton",
              "smith", "jones", "noris"]
NAME_POOL = np.array([f"{a} {b}" for a in FIRST_NAMES for b in LAST_NAMES],
                     dtype=object)

# the two sources as RisingWave's e2e tests declare them (frozen from
# bench.py), formatted by run.py with the event count and the chunk size
PERSON_SOURCE_SQL = (
    "CREATE SOURCE person (id BIGINT, name VARCHAR, email_address VARCHAR,"
    " credit_card VARCHAR, city VARCHAR, state VARCHAR, date_time TIMESTAMP,"
    " extra VARCHAR) WITH (connector='nexmark', nexmark.table='person',"
    " nexmark.max.events='{events}', nexmark.chunk.size='{chunk}')")
AUCTION_SOURCE_SQL = (
    "CREATE SOURCE auction (id BIGINT, item_name VARCHAR, description VARCHAR,"
    " initial_bid BIGINT, reserve BIGINT, date_time TIMESTAMP,"
    " expires TIMESTAMP, seller BIGINT, category BIGINT, extra VARCHAR)"
    " WITH (connector='nexmark', nexmark.table='auction',"
    " nexmark.max.events='{events}', nexmark.chunk.size='{chunk}')")


def person_event_ids(lo, hi):
    """Ids of the person events among events [lo, hi)."""
    ids = np.arange(lo, hi, dtype=np.int64)
    return ids[ids % ref.TOTAL_PROPORTION == 0]


def auction_event_ids(lo, hi):
    """Ids of the auction events among events [lo, hi)."""
    ids = np.arange(lo, hi, dtype=np.int64)
    rem = ids % ref.TOTAL_PROPORTION
    return ids[(rem >= ref.PERSON_PROPORTION)
               & (rem <= ref.AUCTION_PROPORTION)]


def _rand(seed, ids, salt):
    base = np.uint64(int(seed) << 20)
    with np.errstate(over="ignore"):
        return ref.splitmix64(ids.astype(np.uint64)
                              + (base + np.uint64(salt)))


def _persons_before(event_ids):
    """Person events among events [0, n)."""
    full, rem = np.divmod(event_ids, ref.TOTAL_PROPORTION)
    return full * ref.PERSON_PROPORTION + (rem > 0)


def _timestamps(event_ids):
    return (ref.BASE_TIME_USECS
            + event_ids * ref.INTER_EVENT_GAP_USECS).astype(np.int64)


def person_columns(seed, event_ids, cols=("id", "name", "date_time")):
    """{column: array} of the person table at these (person) event ids:
    `id` and `date_time` int64, `name` the strings themselves."""
    ids = (ref.FIRST_PERSON_ID + _persons_before(event_ids)).astype(np.int64)
    out = {}
    if "id" in cols:
        out["id"] = ids
    if "name" in cols:
        first = (_rand(seed, ids, 1)
                 % np.uint64(len(FIRST_NAMES))).astype(np.int64)
        last = (_rand(seed, ids, 2)
                % np.uint64(len(LAST_NAMES))).astype(np.int64)
        out["name"] = NAME_POOL[first * len(LAST_NAMES) + last]
    if "date_time" in cols:
        out["date_time"] = _timestamps(event_ids)
    return out


def auction_columns(seed, event_ids, cols=("seller", "date_time")):
    """{column: int64 array} of the auction table at these (auction) event
    ids. The seller: drawn on the auction's id; nine in ten (draw % 10 != 0)
    among the most recent 1/100 of the persons so far, else any of them."""
    out = {}
    if "seller" in cols:
        full, rem = np.divmod(event_ids, ref.TOTAL_PROPORTION)
        ids = (ref.FIRST_AUCTION_ID + full * ref.AUCTION_PROPORTION + np.clip(
            rem - ref.PERSON_PROPORTION, 0, ref.AUCTION_PROPORTION)
        ).astype(np.int64)
        n_person = np.maximum(_persons_before(event_ids), 1)
        hot = (_rand(seed, ids, 10) % np.uint64(10)) != np.uint64(0)
        pick = _rand(seed, ids, 11)
        span = np.maximum(n_person // HOT_SELLER_RATIO, 1)
        out["seller"] = (ref.FIRST_PERSON_ID + np.where(
            hot, n_person - 1 - ref._mulhi_bound(pick, span),
            ref._mulhi_bound(pick, n_person))).astype(np.int64)
    if "date_time" in cols:
        out["date_time"] = _timestamps(event_ids)
    return out
