"""Device-side EXACT Nexmark generation — bit-identical to the host
connector (`risingwave_tpu/connectors/nexmark.py`).

The host generator is stateless per event id (every column is a pure
function of the id via splitmix64), which makes it directly jittable: the
fused SQL pipeline (`device/fused.py`) generates events IN HBM and never
ships source chunks over the host link — the TPU-native reading of the
reference's in-process datagen source (`src/connector/src/source/nexmark/
source/reader.rs:42`), applied to the design rule "minimise host-device
transfers".

String columns become int64 SURROGATES on device (pool indices / raw
randoms); `decode_column` reconstructs the exact host strings at pull
time. Numeric columns are bit-identical to the host generator — verified
by `tests/test_device_nexmark.py`.
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..connectors.nexmark import (AUCTION_PROPORTION, FIRST_AUCTION_ID,
                                  FIRST_CATEGORY_ID, FIRST_PERSON_ID,
                                  HOT_AUCTION_RATIO, HOT_BIDDER_RATIO,
                                  HOT_SELLER_RATIO, PERSON_PROPORTION,
                                  TOTAL_PROPORTION, _CH_POOL, _CITY_POOL,
                                  _EMAIL_POOL, _NAME_POOL, _STATE_POOL,
                                  _URL_POOL, NexmarkConfig)

_U = jnp.uint64


def splitmix64(x):
    """jnp twin of `connectors/datagen.splitmix64` (wrapping u64 ops)."""
    x = x + _U(0x9E3779B97F4A7C15)
    z = (x ^ (x >> _U(30))) * _U(0xBF58476D1CE4E5B9)
    z = (z ^ (z >> _U(27))) * _U(0x94D049BB133111EB)
    return z ^ (z >> _U(31))


class GenCfg(NamedTuple):
    """Hashable static twin of NexmarkConfig (jit static argument)."""
    seed: int
    base_time_usecs: int
    inter_event_gap_usecs: int
    auction_duration_events: int
    # "" = the nexmark hot/cold entity picks; "zipf:<s>" (s > 1) reshapes
    # the bid auction/bidder picks into a power law — reproducible
    # skewed workloads (host twin: connectors/nexmark.py, bit-identical)
    key_dist: str = ""

    @staticmethod
    def from_config(cfg: NexmarkConfig) -> "GenCfg":
        return GenCfg(cfg.seed, cfg.base_time_usecs,
                      cfg.inter_event_gap_usecs,
                      cfg.auction_duration_events,
                      getattr(cfg, "key_dist", ""))


def key_dist_s(key_dist: str) -> float:
    """Parse 'zipf:<s>' -> s (shared by host and device generators).
    Only s > 1 is supported: the ordinal comes from the bounded-Pareto
    inverse CDF, which needs a finite -1/(s-1) exponent."""
    kind, _, sv = key_dist.partition(":")
    if kind != "zipf":
        raise ValueError(f"unknown key_dist {key_dist!r} "
                         "(supported: 'zipf:<s>', s > 1)")
    s = float(sv) if sv else 1.5
    if s <= 1.0:
        raise ValueError(f"zipf exponent must be > 1, got {s}")
    return s


def _rand(cfg: GenCfg, ids, salt: int):
    return splitmix64(ids.astype(jnp.uint64) + _U((cfg.seed << 20) + salt))


def _mod(r, k: int):
    return (r % _U(k)).astype(jnp.int64)


def _mulhi_bound(r, m):
    """Uniform u64 `r` -> [0, m) via the high 64 bits of r*m (Lemire's
    multiply-shift). 64-bit division-by-vector is pathological for XLA
    backends (measured ~7s of LLVM time PER division on CPU; TPU lowers
    64-bit div to wide-arithmetic emulation) — four 32x32 multiplies and
    shifts compile instantly. The host generator uses the identical
    formula (`connectors/nexmark.py`) so surrogate streams stay
    bit-identical."""
    mask = _U(0xFFFFFFFF)
    a0, a1 = r & mask, r >> 32
    b = m.astype(jnp.uint64)
    b0, b1 = b & mask, b >> 32
    m00 = a0 * b0
    m01 = a0 * b1
    m10 = a1 * b0
    m11 = a1 * b1
    carry = (m00 >> 32) + (m01 & mask) + (m10 & mask)
    return (m11 + (m01 >> 32) + (m10 >> 32)
            + (carry >> 32)).astype(jnp.int64)


def event_kinds(event_ids):
    """0=person, 1=auction, 2=bid (host `_event_kinds`)."""
    m = event_ids % TOTAL_PROPORTION
    return jnp.where(m == 0, 0, jnp.where(m <= AUCTION_PROPORTION, 1, 2))


def _person_count_before(event_ids):
    full, rem = jnp.divmod(event_ids, TOTAL_PROPORTION)
    return full * PERSON_PROPORTION + (rem > 0)


def _auction_count_before(event_ids):
    full, rem = jnp.divmod(event_ids, TOTAL_PROPORTION)
    return full * AUCTION_PROPORTION + jnp.clip(rem - PERSON_PROPORTION, 0,
                                                AUCTION_PROPORTION)


def _timestamps(cfg: GenCfg, event_ids):
    return (cfg.base_time_usecs
            + event_ids * cfg.inter_event_gap_usecs).astype(jnp.int64)


def _hot_pick(rand_hot, rand_pick, n_entities, hot_ratio: int, hot_mod: int):
    """Shared hot-entity ordinal logic (host gen_auctions/gen_bids)."""
    hot = _mod(rand_hot, hot_mod) != 0 if hot_mod == 10 \
        else _mod(rand_hot, 100) < 90
    span = jnp.maximum(n_entities // hot_ratio, 1)
    ord_hot = n_entities - 1 - _mulhi_bound(rand_pick, span)
    ord_cold = _mulhi_bound(rand_pick, n_entities)
    return jnp.where(hot, ord_hot, ord_cold)


def _zipf_ordinal(rand_pick, n_entities, s: float):
    """Power-law entity ordinal (pmf ~ rank^-s, bounded-Pareto inverse
    CDF): rank = floor((1-u)^(-1/(s-1))) clipped to [1, n]. Ordinal 0
    (the FIRST entity) is the hottest — stationary as the entity count
    grows, so the hot key is the same key all run long. Pure f64
    floor/pow over exactly-representable inputs; the host twin
    (connectors/nexmark.py `_zipf_ordinal`) computes the identical
    expression, and tests assert the streams are bit-identical."""
    u = (rand_pick >> _U(11)).astype(jnp.float64) * (2.0 ** -53)
    rank = jnp.floor(jnp.power(1.0 - u, -1.0 / (s - 1.0)))
    rank = jnp.minimum(rank, n_entities.astype(jnp.float64))
    return jnp.maximum(rank, 1.0).astype(jnp.int64) - 1


def gen_table(cfg: GenCfg, table: str, event_ids) -> Dict[str, jnp.ndarray]:
    """All columns of `table` for these event ids, as int64 arrays: a
    pure function of any id array, whatever its order or gaps.

    Every id handed in gets a row regardless of its kind. A caller that
    hands in the table's OWN ids (`own_event_ids`: the fused source)
    masks only the ids past its window; one that walks every id of a
    range (the tests' plain reference) masks the other tables' rows
    with `table_mask`. String columns are surrogates (see SURROGATE)
    decoded host-side by `decode_column`.
    """
    with jax.named_scope("source.gen"):    # HLO metadata only
        return _gen_table(cfg, table, event_ids)


def _gen_table(cfg: GenCfg, table: str, event_ids) -> Dict[str, jnp.ndarray]:
    ts = _timestamps(cfg, event_ids)
    if table == "person":
        ids = (FIRST_PERSON_ID + _person_count_before(event_ids)
               ).astype(jnp.int64)
        fi = _mod(_rand(cfg, ids, 1), len(_NAME_POOL) // 9)   # 11 firsts
        li = _mod(_rand(cfg, ids, 2), 9)                      # 9 lasts
        combo = fi * 9 + li
        return {
            "id": ids,
            "name": combo,
            "email_address": combo,
            "credit_card": _mod(_rand(cfg, ids, 3), 10**16),
            "city": _mod(_rand(cfg, ids, 4), len(_CITY_POOL)),
            "state": _mod(_rand(cfg, ids, 5), len(_STATE_POOL)),
            "date_time": ts,
            "extra": jnp.zeros_like(ids),
        }
    if table == "auction":
        ids = (FIRST_AUCTION_ID + _auction_count_before(event_ids)
               ).astype(jnp.int64)
        n_person = jnp.maximum(_person_count_before(event_ids), 1)
        seller_ord = _hot_pick(_rand(cfg, ids, 10), _rand(cfg, ids, 11),
                               n_person, HOT_SELLER_RATIO, hot_mod=10)
        initial_bid = 100 + _mod(_rand(cfg, ids, 13), 1000)
        return {
            "id": ids,
            "item_name": ids,                 # "item-{id}": derived from id
            "description": _mod(_rand(cfg, ids, 15), 1000),
            "initial_bid": initial_bid,
            "reserve": initial_bid + _mod(_rand(cfg, ids, 14), 1000),
            "date_time": ts,
            "expires": ts + (cfg.auction_duration_events
                             * cfg.inter_event_gap_usecs),
            "seller": (FIRST_PERSON_ID + seller_ord).astype(jnp.int64),
            "category": FIRST_CATEGORY_ID + _mod(_rand(cfg, ids, 12), 5),
            "extra": jnp.zeros_like(ids),
        }
    if table == "bid":
        n_auction = jnp.maximum(_auction_count_before(event_ids), 1)
        n_person = jnp.maximum(_person_count_before(event_ids), 1)
        if cfg.key_dist:
            s = key_dist_s(cfg.key_dist)
            auction_ord = _zipf_ordinal(_rand(cfg, event_ids, 21),
                                        n_auction, s)
            bidder_ord = _zipf_ordinal(_rand(cfg, event_ids, 23),
                                       n_person, s)
        else:
            auction_ord = _hot_pick(_rand(cfg, event_ids, 20),
                                    _rand(cfg, event_ids, 21),
                                    n_auction, HOT_AUCTION_RATIO,
                                    hot_mod=100)
            bidder_ord = _hot_pick(_rand(cfg, event_ids, 22),
                                   _rand(cfg, event_ids, 23),
                                   n_person, HOT_BIDDER_RATIO, hot_mod=100)
        ch = _mod(_rand(cfg, event_ids, 25), len(_CH_POOL))
        return {
            "auction": (FIRST_AUCTION_ID + auction_ord).astype(jnp.int64),
            "bidder": (FIRST_PERSON_ID + bidder_ord).astype(jnp.int64),
            "price": 100 + _mod(_rand(cfg, event_ids, 24), 10_000),
            "channel": ch,
            "url": ch,
            "date_time": ts,
            "extra": jnp.zeros_like(event_ids),
        }
    raise ValueError(f"unknown nexmark table {table!r}")


_KIND = {"person": 0, "auction": 1, "bid": 2}


def table_mask(table: str, event_ids):
    return event_kinds(event_ids) == _KIND[table]


# table -> (events of it in a block of TOTAL_PROPORTION, offset of its
# first): the generator's id -> table rule (`event_kinds`) as intervals
_SHARE = {
    "person": (PERSON_PROPORTION, 0),
    "auction": (AUCTION_PROPORTION, PERSON_PROPORTION),
    "bid": (TOTAL_PROPORTION - PERSON_PROPORTION - AUCTION_PROPORTION,
            PERSON_PROPORTION + AUCTION_PROPORTION),
}


def own_rows_bound(table: str, epoch_events: int) -> int:
    """The most rows of `table` any window of `epoch_events` consecutive
    event ids can hold (a window touches at most events // 50 + 2
    blocks)."""
    return (epoch_events // TOTAL_PROPORTION + 2) * _SHARE[table][0]


def source_lanes(table: str, epoch_events: int) -> int:
    """Lanes a device source of `table` makes an epoch: the pow2 bucket
    of the table's row bound, and never more than the epoch's events
    (bid at a pow2 cadence: 46 of 50 events are its own). Static:
    arithmetic on the generator's proportions and the cadence, nothing
    observed."""
    from .capacity import bucket
    return min(epoch_events, bucket(own_rows_bound(table, epoch_events)))


def own_event_ids(table: str, event_lo, lanes: int):
    """The first `lanes` event ids of `table` at or after `event_lo`,
    ascending (int64): the row of ordinal q is event
    (q // p) * 50 + o + q % p. `event_lo` is a traced scalar; the lane
    arithmetic stays 32-bit (lanes + p < 2^31), only the block base is
    64-bit."""
    p, o = _SHARE[table]
    full, rem = jnp.divmod(event_lo, TOTAL_PROPORTION)
    before = full * p + jnp.clip(rem - o, 0, p)   # own rows below event_lo
    b_full, b_rem = jnp.divmod(before, p)
    q, r = jnp.divmod(
        b_rem.astype(jnp.int32) + jnp.arange(lanes, dtype=jnp.int32), p)
    return ((b_full + q.astype(jnp.int64)) * TOTAL_PROPORTION
            + (o + r).astype(jnp.int64))


# ---------------------------------------------------------------------------
# surrogate metadata: how the host decodes device int64 columns
# ---------------------------------------------------------------------------

# column -> ("num",) exact int64 | ("ts",) timestamp usecs |
#           ("pool", pool) index into object pool | ("zfill16",) |
#           ("item_name",) "item-{v}" | ("desc",) "desc-{v}" | ("empty",)
SURROGATE: Dict[str, Dict[str, Tuple]] = {
    "person": {
        "id": ("num",), "name": ("pool", _NAME_POOL),
        "email_address": ("pool", _EMAIL_POOL), "credit_card": ("zfill16",),
        "city": ("pool", _CITY_POOL), "state": ("pool", _STATE_POOL),
        "date_time": ("ts",), "extra": ("empty",),
    },
    "auction": {
        "id": ("num",), "item_name": ("item_name",), "description": ("desc",),
        "initial_bid": ("num",), "reserve": ("num",), "date_time": ("ts",),
        "expires": ("ts",), "seller": ("num",), "category": ("num",),
        "extra": ("empty",),
    },
    "bid": {
        "auction": ("num",), "bidder": ("num",), "price": ("num",),
        "channel": ("pool", _CH_POOL), "url": ("pool", _URL_POOL),
        "date_time": ("ts",), "extra": ("empty",),
    },
}


def decode_column(spec: Tuple, vals: np.ndarray) -> np.ndarray:
    """Surrogate int64s -> the exact host-generator column values."""
    kind = spec[0]
    if kind in ("num", "ts"):
        return vals
    if kind == "pool":
        return spec[1][vals]
    if kind == "zfill16":
        return np.char.zfill(vals.astype("U16"), 16).astype(object)
    if kind == "item_name":
        return np.char.add("item-", vals.astype("U20")).astype(object)
    if kind == "desc":
        return np.char.add("desc-", vals.astype("U4")).astype(object)
    if kind == "empty":
        return np.full(len(vals), "", dtype=object)
    raise ValueError(f"unknown surrogate spec {spec!r}")


def column_bounds(cfg: GenCfg, table: str, col: str,
                  max_events: Optional[int]) -> Tuple[int, int]:
    """Inclusive (lo, hi) value bounds for a column given the event
    horizon — the interval analysis the fused key packer builds on.
    Unbounded sources assume a 2^40-event horizon (loud device-side
    bounds checks still back this up)."""
    n = max_events if max_events is not None else 1 << 40
    ts_lo = cfg.base_time_usecs
    ts_hi = cfg.base_time_usecs + n * cfg.inter_event_gap_usecs
    n_person = n // TOTAL_PROPORTION * PERSON_PROPORTION + 2
    n_auction = n // TOTAL_PROPORTION * AUCTION_PROPORTION + 4
    b: Dict[Tuple[str, str], Tuple[int, int]] = {
        ("person", "id"): (FIRST_PERSON_ID, FIRST_PERSON_ID + n_person),
        ("person", "name"): (0, len(_NAME_POOL) - 1),
        ("person", "email_address"): (0, len(_EMAIL_POOL) - 1),
        ("person", "credit_card"): (0, 10**16),
        ("person", "city"): (0, len(_CITY_POOL) - 1),
        ("person", "state"): (0, len(_STATE_POOL) - 1),
        ("person", "date_time"): (ts_lo, ts_hi),
        ("person", "extra"): (0, 0),
        ("auction", "id"): (FIRST_AUCTION_ID, FIRST_AUCTION_ID + n_auction),
        ("auction", "item_name"): (FIRST_AUCTION_ID,
                                   FIRST_AUCTION_ID + n_auction),
        ("auction", "description"): (0, 999),
        ("auction", "initial_bid"): (100, 1099),
        ("auction", "reserve"): (100, 2198),
        ("auction", "date_time"): (ts_lo, ts_hi),
        ("auction", "expires"): (ts_lo, ts_hi + cfg.auction_duration_events
                                 * cfg.inter_event_gap_usecs),
        ("auction", "seller"): (FIRST_PERSON_ID, FIRST_PERSON_ID + n_person),
        ("auction", "category"): (FIRST_CATEGORY_ID, FIRST_CATEGORY_ID + 4),
        ("auction", "extra"): (0, 0),
        ("bid", "auction"): (FIRST_AUCTION_ID, FIRST_AUCTION_ID + n_auction),
        ("bid", "bidder"): (FIRST_PERSON_ID, FIRST_PERSON_ID + n_person),
        ("bid", "price"): (100, 10_099),
        ("bid", "channel"): (0, len(_CH_POOL) - 1),
        ("bid", "url"): (0, len(_URL_POOL) - 1),
        ("bid", "date_time"): (ts_lo, ts_hi),
        ("bid", "extra"): (0, 0),
    }
    return b[(table, col)]
