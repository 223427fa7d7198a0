"""The fused MV on the host, as columns.

A pull of the terminal MV state gives, per MV column, the values in the
device's own domain (int64 / f64 / surrogates) and a null mask
(`MVColumns`). A SELECT formats all of them into row tuples; the
checkpoint's mirror (`FusedJob._persist_mv`) keeps the last mirrored image
in the same columnar form (`MirrorImage`: the rows' state-table keys as one
ascending array beside the raw columns), finds what changed by array work
(`diff_images`) and formats only the rows it writes (`mirror_batch`).
Python objects are made once a row that is written, in bulk, and never to
compare.
"""
from __future__ import annotations

from decimal import Decimal
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..core.chunk import Column
from ..core.dtypes import DataType, TypeKind
from ..core.encoding import key_bytes_list, key_is_fixed_width

_NUMBERS = (("num",), ("ts",))


def format_col(dtype: DataType, vals: np.ndarray,
               nulls: Optional[np.ndarray]) -> List[Any]:
    """Device int64/f64 column -> host Python values matching the host
    executors' state-table representation exactly, a whole column at a
    time."""
    kind = dtype.kind
    if kind == TypeKind.DECIMAL:
        out = list(map(Decimal, vals.astype(np.int64, copy=False).tolist()))
    elif kind in (TypeKind.FLOAT32, TypeKind.FLOAT64):
        out = vals.astype(np.float64, copy=False).tolist()
    elif kind == TypeKind.BOOLEAN:
        out = vals.astype(np.bool_, copy=False).tolist()
    else:
        out = vals.astype(np.int64, copy=False).tolist()
    return _with_nulls(out, nulls)


def _with_nulls(out: List[Any], nulls: Optional[np.ndarray]) -> List[Any]:
    if nulls is None or not nulls.any():
        return out
    arr = np.empty(len(out), dtype=object)
    arr[:] = out
    arr[nulls] = None
    return arr.tolist()


class MVColumns:
    """The MV as one pull left it on the host: `n` rows, per column
    `vals[k]` (device domain) and `nulls[k]` (bool mask, or None for a
    column that is never NULL); `strs[k]` the decoded strings of a VARCHAR
    column once `decode()` ran."""

    def __init__(self, dtypes: Sequence[DataType], decoders: Sequence[Tuple],
                 pulled: Sequence[Tuple[Any, Any]], n: int):
        self.dtypes = list(dtypes)
        self.decoders = list(decoders)
        self.n = n
        self.vals = [np.asarray(v) for v, _ in pulled]
        self.nulls = [None if m is None else np.asarray(m).astype(np.bool_)
                      for _, m in pulled]
        self.strings = [k for k, d in enumerate(self.decoders)
                        if d not in _NUMBERS]
        self.strs: Dict[int, np.ndarray] = {}

    def decode(self) -> None:
        """Surrogates -> strings, every VARCHAR column whole."""
        from .nexmark_gen import decode_column
        for k in self.strings:
            self.strs[k] = np.asarray(decode_column(
                self.decoders[k], self.vals[k].astype(np.int64)), object)

    def rows(self, idx: Optional[np.ndarray] = None) -> List[Tuple]:
        """Row tuples (of the rows `idx`, in that order; all by default):
        each column formatted whole, rows from `zip`."""
        def take(a):
            return a if idx is None or a is None else a[idx]
        cols = []
        for k, dt in enumerate(self.dtypes):
            nulls = take(self.nulls[k])
            if k in self.strs:
                cols.append(_with_nulls(take(self.strs[k]).tolist(), nulls))
            else:
                cols.append(format_col(dt, take(self.vals[k]), nulls))
        return list(zip(*cols)) if cols else []


class MirrorImage:
    """The MV as the state table last took it: `keys`, the rows'
    state-table keys in ascending order — one fixed-width `S` array where
    every key has the same width (`tobytes()` gives the exact bytes), an
    object array of `bytes` otherwise — beside the raw value columns and
    null masks in that order. `known` False: only the keys are known (a
    recovered job's table), so every key the next pull still has is
    written again."""

    __slots__ = ("keys", "vals", "nulls", "known")

    def __init__(self, keys: Optional[np.ndarray] = None, vals=(), nulls=(),
                 known=True):
        self.keys = np.empty(0, "S1") if keys is None else keys
        self.vals = list(vals)
        self.nulls = list(nulls)
        self.known = known

    def __len__(self) -> int:
        return len(self.keys)

    @classmethod
    def of_keys(cls, keys: List[bytes]) -> "MirrorImage":
        """The image of a table of which only the (ascending) keys are
        known."""
        return cls(key_array(keys), known=False)


def key_array(keys: List[bytes]) -> np.ndarray:
    """A list of encoded keys as one sortable array: `S<w>` where all are
    `w` bytes wide (numpy compares those as bytes do), objects otherwise."""
    widths = set(map(len, keys))
    if len(widths) == 1 and (w := widths.pop()):
        return np.frombuffer(b"".join(keys), dtype=f"S{w}")
    out = np.empty(len(keys), dtype=object)
    out[:] = keys
    return out


def key_list(keys: np.ndarray, idx: np.ndarray) -> List[bytes]:
    """`keys[idx]` as exact `bytes`."""
    sel = keys[idx]
    if sel.dtype == object:
        return sel.tolist()
    return key_bytes_list(
        sel.view(np.uint8).reshape(len(sel), sel.dtype.itemsize))


def _as_objects(keys: np.ndarray) -> np.ndarray:
    if keys.dtype == object:
        return keys
    out = np.empty(len(keys), dtype=object)
    out[:] = key_list(keys, slice(None))
    return out


_NONE = np.empty(0, np.int64)


def diff_images(old: MirrorImage, new: MirrorImage
                ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """A sorted merge of two images' keys: (`ins`, `upd`, `dels`) — the
    positions in `new` of the keys only it has, the positions in `new` of
    the keys both have whose values or null masks differ in any column
    (all of them where `old` knows no values), the positions in `old` of
    the keys only it has. Each ascending."""
    if not len(old):
        return np.arange(len(new)), _NONE, _NONE
    if not len(new):
        return _NONE, _NONE, np.arange(len(old))
    okeys, nkeys = old.keys, new.keys
    if okeys.dtype != nkeys.dtype:
        # two forms (a NULL came into the pk, a table read back): as bytes
        okeys, nkeys = _as_objects(okeys), _as_objects(nkeys)
    pos = np.searchsorted(okeys, nkeys)
    # a new key above every old one lands on len(old): held against the
    # last old key it reads as absent, which it is
    pos = np.minimum(pos, len(okeys) - 1)
    hit = np.asarray(okeys[pos] == nkeys, np.bool_)
    at_new = np.flatnonzero(hit)
    at_old = pos[at_new]
    gone = np.ones(len(okeys), np.bool_)
    gone[at_old] = False
    if old.known:
        changed = np.zeros(len(at_new), np.bool_)
        for ov, om, nv, nm in zip(old.vals, old.nulls, new.vals, new.nulls):
            differs = ov[at_old] != nv[at_new]
            if om is None and nm is None:
                changed |= differs
                continue
            om = np.zeros(len(at_old), np.bool_) if om is None else om[at_old]
            nm = np.zeros(len(at_new), np.bool_) if nm is None else nm[at_new]
            # what lies under a NULL is not part of the row
            changed |= (om != nm) | (differs & ~nm)
        at_new = at_new[changed]
    return np.flatnonzero(~hit), at_new, np.flatnonzero(gone)


def image_of(cols: MVColumns, table) -> Tuple[MirrorImage, np.ndarray,
                                              Optional[List[Tuple]]]:
    """The pulled MV as an image: its keys from the key matrix
    (`StateTable.key_matrix` over the raw pk columns — for fixed-width
    kinds the device's numbers are the table's), or, where that gives
    None (a pk column that is not fixed-width, a NULL in the pk), from the
    per-row `key_of` over all formatted rows. Returns the image, the
    order that sorted the pull into it, and the formatted rows where the
    keys needed them (pull order)."""
    rows = None
    mat = None
    if key_is_fixed_width(table.pk_dtypes):
        mat = table.key_matrix({
            i: Column(table.dtypes[i], cols.vals[i],
                      None if cols.nulls[i] is None else ~cols.nulls[i])
            for i in {*table.pk_indices, *table.dist_key_indices}})
    if mat is not None:
        keys = (mat.view(f"S{mat.shape[1]}").ravel() if len(mat)
                else np.empty(0, f"S{mat.shape[1]}"))
    else:
        rows = cols.rows()
        keys = key_array([table.key_of(r) for r in rows])
    order = np.argsort(keys, kind="stable")
    image = MirrorImage(keys[order], [v[order] for v in cols.vals],
                        [None if m is None else m[order]
                         for m in cols.nulls])
    return image, order, rows


def mirror_batch(old: MirrorImage, cols: MVColumns, table
                 ) -> Tuple[MirrorImage, List[bytes], List[Optional[Tuple]],
                            Dict[str, Any]]:
    """One mirror of the pulled MV against the last image: the new image,
    and the state table's batch — the keys to put, ascending, beside
    their rows, then the keys the MV no longer has, ascending, beside
    None — with only the rows that are written formatted. The counts say
    which of the three sets they are in and whether the keys came from
    the matrix."""
    new, order, rows = image_of(cols, table)
    ins, upd, dels = diff_images(old, new)
    put = np.sort(np.concatenate([ins, upd])) if len(upd) else ins
    keys = key_list(new.keys, put)
    if rows is None:
        put_rows: List[Optional[Tuple]] = cols.rows(order[put])
    else:
        put_rows = [rows[i] for i in order[put].tolist()]
    # the tombstones go behind the puts: two ascending runs, one where
    # the MV lost no key
    keys += key_list(old.keys, dels)
    put_rows += [None] * len(dels)
    counts = {"rows": cols.n, "inserted": len(ins), "updated": len(upd),
              "deleted": len(dels), "keys_vectorised": rows is None}
    return new, keys, put_rows, counts
