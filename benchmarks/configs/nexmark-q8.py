"""nexmark-q8: the SQL, the plain reference, the control, least bytes.

NEXmark q8 "Monitor New Users": the persons who opened an auction within the
10 s tumbling window in which they registered. The reference is written from
the query's meaning, numpy over `lib/nexmark_ref_entities.py` (the frozen
person and auction streams), nothing of the program and nothing of its plan.

The control is NOT the other configurations'. Both sides of q8 are
de-duplicating group-bys, so a stream whose last epoch is applied twice (an
at-least-once replay) gives the SAME MV (`replayed` shows it): a duplicate is
invisible to this query, and a control built on one would pass a broken run.
`control` breaks exactly-once the other way: the last epoch is LOST.
"""
import numpy as np

import nexmark_ref_entities as ent

MV = "nexmark_q8"
SOURCES = [ent.PERSON_SOURCE_SQL, ent.AUCTION_SOURCE_SQL]
MV_SQL = """CREATE MATERIALIZED VIEW nexmark_q8 AS
SELECT P.id, P.name, P.starttime
FROM (
    SELECT id, name, window_start AS starttime, window_end AS endtime
    FROM TUMBLE(person, date_time, INTERVAL '10' SECOND)
    GROUP BY id, name, window_start, window_end
) P
JOIN (
    SELECT seller, window_start AS starttime, window_end AS endtime
    FROM TUMBLE(auction, date_time, INTERVAL '10' SECOND)
    GROUP BY seller, window_start, window_end
) A ON P.id = A.seller AND P.starttime = A.starttime
   AND P.endtime = A.endtime"""
READ_SQL = "SELECT * FROM nexmark_q8"
WINDOW_USECS = 10_000_000


def normalise(rows):
    """MV rows as read -> a list of (int id, str name, int window start in
    microseconds) tuples (a multiset)."""
    return [(int(i), str(name), int(w)) for i, name, w in rows]


def _key(ids, date_time):
    """(id, tumbling window) as one int64: the window's number in the high
    half, the id in the low. A window's end is its start + 10 s, so equal
    starts are equal windows."""
    assert int(ids.max(initial=0)) < 1 << 32
    return ((date_time // WINDOW_USECS) << 32) | ids


def _rows(person_ids, auction_ids, seed):
    """The distinct (id, name, window) of persons whose (id, window) is
    among the distinct (seller, window) of auctions."""
    p = ent.person_columns(seed, person_ids)
    a = ent.auction_columns(seed, auction_ids)
    sellers = np.unique(_key(a["seller"], a["date_time"]))
    hit = np.isin(_key(p["id"], p["date_time"]), sellers)
    start = (p["date_time"][hit] // WINDOW_USECS) * WINDOW_USECS
    return sorted(set(zip(p["id"][hit].tolist(), p["name"][hit].tolist(),
                          start.tolist())))


def reference(seed, events):
    """The MV over events [0, events) of the seeded stream."""
    return _rows(ent.person_event_ids(0, events),
                 ent.auction_event_ids(0, events), seed)


def control(seed, events, epoch_events):
    """The reference with the exactly-once guarantee broken, the half of it
    this query can show: the last epoch of the stream is LOST (events
    [0, events - epoch_events)), as a commit that never became durable."""
    return reference(seed, max(0, events - epoch_events))


def replayed(seed, events, epoch_events):
    """The reference over the stream with its last epoch applied twice (the
    other configurations' control). For q8 it equals `reference`: both
    group-bys de-duplicate, so a duplicate cannot be seen. Kept to show it."""
    lo = max(0, events - epoch_events)
    return _rows(
        np.concatenate([ent.person_event_ids(0, events),
                        ent.person_event_ids(lo, events)]),
        np.concatenate([ent.auction_event_ids(0, events),
                        ent.auction_event_ids(lo, events)]), seed)


def counts(seed, events, epoch_events):
    """What the query has to touch, from the events alone: rows consumed,
    groups new on each side, groups an epoch meets again (an epoch's
    distinct (seller, window) or (id, name, window) that an earlier epoch
    already made), MV rows."""
    persons = auctions = met_again = 0
    seen = {"person": np.zeros(0, np.int64), "auction": np.zeros(0, np.int64)}
    for lo in range(0, events, epoch_events):
        hi = min(events, lo + epoch_events)
        p = ent.person_columns(seed, ent.person_event_ids(lo, hi),
                               ("id", "date_time"))
        a = ent.auction_columns(seed, ent.auction_event_ids(lo, hi))
        persons += len(p["id"])
        auctions += len(a["seller"])
        for side, keys in (("person", _key(p["id"], p["date_time"])),
                           ("auction", _key(a["seller"], a["date_time"]))):
            keys = np.unique(keys)
            old = np.isin(keys, seen[side])
            met_again += int(old.sum())
            seen[side] = np.concatenate([seen[side], keys[~old]])
    return {"persons": persons, "auctions": auctions,
            "person_groups": len(seen["person"]),
            "auction_groups": len(seen["auction"]),
            "groups_met_again": met_again,
            "mv_changes": len(reference(seed, events)),
            "epochs": -(-events // epoch_events)}


def least_bytes(c):
    """Least bytes the query moves over the window: the three consumed
    person columns and the two consumed auction columns once a row; every
    new group's key (four columns a person group, three an auction group)
    written once on its agg and once on its join side; every group met
    again read once (an auction group's three columns: a person's id is
    new by construction); every MV row (three values) written once. 8-byte
    values throughout."""
    return (c["persons"] * 3 * 8 + c["auctions"] * 2 * 8
            + c["person_groups"] * 4 * 8 * 2 + c["auction_groups"] * 3 * 8 * 2
            + c["groups_met_again"] * 3 * 8 + c["mv_changes"] * 3 * 8)
