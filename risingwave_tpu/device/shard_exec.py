"""Mesh-sharded execution of fused epoch programs: one job, all chips.

This is the scale lever the ROADMAP names: a `FusedJob` whose node state
arrays carry a leading SHARD axis (`parallel/mesh.py` `SHARD_AXIS`,
vnode-keyed `PartitionSpec`) and whose per-node epoch steps run as
`shard_map`'d programs over the 1-D device mesh. The paper's north star
(`psum`/`ppermute` exchange over ICI with vnode-sharded state) maps here
as:

* **State partitioning** — every stateful node's arrays gain a leading
  `[n_shards, ...]` axis; shard s owns the contiguous vnode block
  `vnode_block_bounds(n)[s] : [s+1]` of group/join keys (contiguous
  blocks keep a shard's key range compact for the sorted-run state).

* **In-program exchange** — the cross-vnode shuffle joins/aggs need
  (rows whose key hashes to another shard's vnode block) is an
  `all_to_all` bucket exchange INSIDE the traced program: each shard
  CRC32-hashes its rows to vnodes, buckets them into a
  `[n_shards, exch]` send buffer, and the collective swaps buckets over
  ICI — no host socket frames, no host round trip. "Global Hash Tables
  Strike Back!" motivates exactly this local-bucket-then-merge shape.
  WHICH inputs exchange on WHICH key columns is declared by the node
  (`Node.shard_spec`, the fuse-planner refactor), not hardcoded here.

* **psum'd global stats** — each node's stats scalars reduce in-program:
  row-flow counters by `psum`, capacity needs / violation flags by
  `pmax` (the per-shard HIGH-WATER is what sizes per-shard capacity),
  so the job-level stats accumulator and the whole capacity lifecycle
  (overflow detection, predictive growth, cascade-free replay) work
  UNCHANGED on sharded programs.

* **Exchange capacity** — the `[n_shards, exch]` send bucket is a real
  capacity slot ("exch") on Agg/Join nodes: bucket overflow is detected
  by the `exch` stat (max bucket count, pmax'd), and the normal
  grow+replay path resizes it (per-epoch-bounded — flat headroom, never
  horizon-extrapolated). Rows dropped by an overflowing epoch are
  discarded with that epoch's state by the replay, so correctness is
  never at the mercy of the initial guess.

Semantics: sharding is an execution detail. Keys are partitioned, all
arithmetic is over int64/f64 values whose per-key row order is preserved
by the exchange (source shards cover contiguous event-id blocks and the
bucket flatten is src-major, so each key sees its rows in event order,
the same order the single-chip sort produces with jax's stable sorts) —
an n-shard run is bit-identical to the 1-shard run, asserted by
tests/test_mesh_fused.py.
"""
from __future__ import annotations

from typing import Any, List, Optional, Tuple

import numpy as np

from ..core.vnode import VNODE_COUNT
from ..parallel.mesh import (SHARD_AXIS, data_shards, mesh_replicas,
                             shard_of_vnode, state_sharding)
from ..parallel.mesh import shard_map as _shard_map


def mesh_fingerprint(mesh) -> Optional[Tuple]:
    """Hashable, process-stable identity of a mesh for dispatch keys:
    axis layout + the member device ids (two meshes over different
    device sets must never share an executable)."""
    if mesh is None:
        return None
    return (tuple(mesh.axis_names), tuple(mesh.devices.shape),
            tuple(int(d.id) for d in mesh.devices.flat))


# ---------------------------------------------------------------------------
# state lifting: local pytree <-> [n_shards, ...] mesh-sharded pytree
# ---------------------------------------------------------------------------


def lift_tree(tree, mesh):
    """Broadcast every leaf of a local state pytree to [n_shards, ...]
    and place it sharded on the mesh (vnode-keyed PartitionSpec on the
    leading axis). Initial states are identical empty shards, so a
    broadcast IS the correct per-shard initialization."""
    import jax
    n = data_shards(mesh)
    sh = state_sharding(mesh)

    def lift(x):
        a = np.asarray(x)
        return jax.device_put(
            np.broadcast_to(a[None], (n,) + a.shape).copy(), sh)

    return jax.tree_util.tree_map(lift, tree)


def _drop(tree):
    """shard_map local view [1, ...] -> the node-local [...] pytree."""
    import jax
    return jax.tree_util.tree_map(lambda x: x[0], tree)


def _lift1(tree):
    """Node-local [...] pytree -> shard_map local output [1, ...]."""
    import jax
    return jax.tree_util.tree_map(lambda x: x[None], tree)


def _spec_sharded(tree):
    import jax
    from jax.sharding import PartitionSpec as P
    return jax.tree_util.tree_map(lambda _: P(SHARD_AXIS), tree)


def _spec_replicated(tree):
    import jax
    from jax.sharding import PartitionSpec as P
    return jax.tree_util.tree_map(lambda _: P(), tree)


def sds_sharded(tree, mesh):
    """ShapeDtypeStruct mirror of a [n_shards, ...] pytree with the mesh
    sharding attached — what the AOT compile service lowers sharded
    signatures against (a plain SDS would lower a single-device layout
    and the executable would reject the mesh-placed epoch arrays)."""
    import jax
    sh = state_sharding(mesh)
    return jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=sh), tree)


def sharded_resize(node, state, caps, mesh):
    """Apply a node's LOCAL `cap_resize` across the shard axis: vmap maps
    the axis-0 pads of grow_state/ms_grow/grow_side onto axis 1 of the
    lifted arrays (the node's attribute updates happen once, at trace),
    then re-place on the mesh. Rare path — only growth replays come here.
    """
    import jax
    if state is None or not jax.tree_util.tree_leaves(state):
        node.cap_resize(state, caps)       # attr-only (e.g. exch) update
        return state
    new = jax.vmap(lambda st: node.cap_resize(st, caps))(state)
    sh = state_sharding(mesh)
    return jax.tree_util.tree_map(lambda x: jax.device_put(x, sh), new)


# ---------------------------------------------------------------------------
# the in-program bucket exchange (all_to_all over ICI)
# ---------------------------------------------------------------------------


def _route_dest(vn, n: int, bounds: Optional[Tuple[int, ...]]):
    """Owning shard of each vnode under the routing policy: the uniform
    contiguous-block formula (`shard_of_vnode`) when `bounds` is None,
    otherwise the custom (rebalanced) block bounds — shard s owns
    [bounds[s], bounds[s+1]); empty blocks (equal consecutive bounds)
    are legal and are the point of a rebalance: a hot histogram bucket
    gets a shard to itself."""
    import jax.numpy as jnp
    if bounds is None:
        return shard_of_vnode(vn.astype(jnp.int64), n, VNODE_COUNT
                              ).astype(jnp.int32)
    dest = jnp.zeros(vn.shape, jnp.int32)
    for b in bounds[1:-1]:
        dest = dest + (vn >= b).astype(jnp.int32)
    return dest


def _pmax(x):
    """Max over the shard axis as all_gather + max: the TPU compiler
    lowers a 64-bit all-reduce for Sum only (`lax.pmax` of an int64 is
    UNIMPLEMENTED there), and every capacity stat is an int64."""
    import jax
    import jax.numpy as jnp
    return jnp.max(jax.lax.all_gather(x, SHARD_AXIS), axis=0)


def _exchange_local(mesh, node, xi: int, d, abstract: bool,
                    bounds: Optional[Tuple[int, ...]] = None,
                    hot_keys: Tuple[int, ...] = (), hot_side: int = 1):
    """Shard-local body: hash rows to their owning shard's vnode block,
    bucket into the [n_shards, exch] send buffer, all_to_all, flatten.
    The routing key columns and whether row identity rides along come
    from the node's declarative shard spec (`Node.shard_spec`).

    Routing policy (all trace-static, all exchange-only — node steps
    never see it): `bounds` overrides the uniform vnode-block layout
    (barrier-time rebalancing); `hot_keys` (40-bit-truncated, the
    heavy-hitter evidence format) arms hot-key replication on pk-
    carrying exchanges: input `hot_side`'s hot rows BROADCAST to every
    shard (build rows replicate), the other input's hot rows salt
    round-robin by row identity (probe work spreads; a row and its
    later retraction share a pk, hence a shard). Every pair of one hot
    key is still produced on exactly one shard — the shard owning the
    salted-side row — so netting and the pair MV stay exact.

    `abstract=True` is the shape-faithful mirror used for AOT aval walks
    (collectives replaced by shape-identities; needs no mesh axis)."""
    import jax
    import jax.numpy as jnp
    from ..core.vnode import compute_vnodes_jnp
    from .fused import Delta
    n = data_shards(mesh)
    exch = node.exch
    ex = node.shard_spec().exchanges[xi]
    with jax.named_scope("exchange.route"):
        if ex.packed:
            # pre-combined deltas carry the packed key verbatim (column 0)
            key = d.cols[ex.key_idx[0]]
        else:
            key = node.pack.pack([d.cols[i] for i in ex.key_idx])
        vn = compute_vnodes_jnp(key, VNODE_COUNT)
        dest = _route_dest(vn, n, bounds)
        live = d.mask & (d.sign != 0)
        bcast = None
        if hot_keys:
            from .skew_stats import SK_KEY_MASK
            k40 = key & SK_KEY_MASK
            is_hot = jnp.zeros(key.shape, bool)
            for hk in hot_keys:
                is_hot = is_hot | (k40 == hk)
            is_hot = is_hot & live
            if xi == hot_side or not ex.carry_pk or d.pk is None:
                bcast = is_hot             # replicated (build) side
            else:
                # salted (probe) side: deterministic by row identity
                dest = jnp.where(is_hot, (d.pk % n).astype(jnp.int32), dest)
    # only the columns the node declares it reads ship over ICI; the
    # routed delta zero-fills the rest (never touched by declaration)
    ncols = len(d.cols)
    refs = list(ex.ref_idx) if ex.ref_idx is not None else list(range(ncols))
    with jax.named_scope("exchange.bucket"):
        arrays: List[Any] = [d.cols[i] for i in refs] \
            + [jnp.where(live, d.sign, 0).astype(jnp.int32)]
        if ex.carry_pk:
            arrays.append(d.pk)
        onehot = (dest[None, :] == jnp.arange(n, dtype=jnp.int32)[:, None]) \
            & live[None, :]
        if bcast is not None:
            onehot = onehot | bcast[None, :]
        counts = jnp.sum(onehot, axis=1)
        # max bucket fill = the "exch" capacity stat; > exch means rows
        # were dropped this epoch -> sync detects overflow, grows, replays.
        # Replicated copies count per destination — their HBM is real.
        need = jnp.max(counts).astype(jnp.int64)
        pos = jnp.cumsum(onehot, axis=1) - 1
        bufs = []
        if bcast is None:
            # single-destination fast path (no hot keys): one [B] scatter
            posr = jnp.take_along_axis(pos, dest[None, :].astype(jnp.int32),
                                       axis=0)[0]
            rdest = jnp.where(live, dest, n)  # OOB rows drop out of the set
            for a in arrays:
                buf = jnp.zeros((n, exch), dtype=a.dtype)
                bufs.append(buf.at[rdest, posr].set(a, mode="drop"))
        else:
            # multi-destination scatter: a broadcast row occupies its slot
            # in EVERY destination bucket, in the same row order
            dd = jnp.arange(n, dtype=jnp.int32)[:, None]
            idx = jnp.where(onehot, pos, exch)     # OOB -> dropped
            for a in arrays:
                buf = jnp.zeros((n, exch), dtype=a.dtype)
                bufs.append(buf.at[dd, idx].set(
                    jnp.broadcast_to(a[None], (n,) + a.shape), mode="drop"))
    # live rows each destination receives this epoch (the "xin" stats):
    # this shard's per-destination counts, summed over the source shards
    # beside the collective that swaps the buckets
    sent = counts.astype(jnp.int64)
    if abstract:
        recv = bufs                        # all_to_all is shape-preserving
    else:
        with jax.named_scope("exchange.a2a"):
            recv = [jax.lax.all_to_all(b, SHARD_AXIS, split_axis=0,
                                       concat_axis=0, tiled=False)
                    for b in bufs]
            need = _pmax(need)
            sent = jax.lax.psum(sent, SHARD_AXIS)
    rows_in = [sent[s] for s in range(n)]
    rb = n * exch
    rs = [r.reshape(rb) for r in recv]
    sign = rs[len(refs)]
    at = {c: k for k, c in enumerate(refs)}
    cols = [rs[at[i]] if i in at else jnp.zeros(rb, dtype=d.cols[i].dtype)
            for i in range(ncols)]
    out = Delta(cols, sign, sign != 0,
                pk=rs[len(refs) + 1] if ex.carry_pk else None)
    return out, need, rows_in


def exchange_apply(mesh, node, xi: int, delta, abstract: bool = False,
                   bounds: Optional[Tuple[int, ...]] = None,
                   hot_keys: Tuple[int, ...] = (), hot_side: int = 1):
    """Global-view exchange of one input delta: route every live row to
    the shard owning its key's vnode block (under the routing policy —
    see `_exchange_local`). Returns (routed delta with
    [n_shards, n_shards * exch] rows per shard, max-bucket-fill stat,
    the live rows each destination shard receives, one scalar a shard)."""
    import jax

    if abstract:
        import jax.numpy as jnp
        n = data_shards(mesh)
        out, need, rows_in = _exchange_local(
            mesh, node, xi, _drop(delta), True, bounds, hot_keys, hot_side)
        lift = lambda t: jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), t)
        return lift(out), need, rows_in

    def local(d):
        out, need, rows_in = _exchange_local(
            mesh, node, xi, _drop(d), False, bounds, hot_keys, hot_side)
        return _lift1(out), need, rows_in

    # specs need only the output TREE STRUCTURE (one P(shard) per leaf);
    # the abstract body mirrors it exactly
    out_sds = jax.eval_shape(
        lambda d: _exchange_local(mesh, node, xi, _drop(d), True,
                                  bounds, hot_keys, hot_side), delta)
    fn = _shard_map(local, mesh=mesh,
                    in_specs=(_spec_sharded(delta),),
                    out_specs=(_spec_sharded(out_sds[0]),
                               _spec_replicated(out_sds[1]),
                               _spec_replicated(out_sds[2])),
                    check_vma=False)
    return fn(delta)


_EXCH_JIT = {}
# pre-compiled exchange executables (the checkpoint-time policy switch
# pre-warms its re-routed exchanges here — `prewarm_exchange`), keyed by
# (mesh fingerprint, node shape, stage, full routing salt, input avals).
_EXCH_AOT: dict = {}
# dispatch accounting: `inline` counts DISTINCT signatures that took the
# trace-on-dispatch path (a policy switch must add none — that is the
# zero-fresh-compile assertion), `aot_hits` counts pre-warmed dispatches
EXCH_STATS = {"aot_hits": 0, "calls": 0}
_EXCH_INLINE: set = set()


def delta_sds(tree):
    """ShapeDtypeStruct mirror (sharding-carrying) of a live delta — the
    avals `prewarm_exchange` lowers the re-routed exchange against."""
    import jax

    def sds(l):
        return jax.ShapeDtypeStruct(l.shape, l.dtype,
                                    sharding=getattr(l, "sharding", None))

    return jax.tree_util.tree_map(sds, tree)


def _exch_key(mesh, node, xi: int, salt, delta_tree) -> Tuple:
    import jax
    from .fused import node_shape_key
    leaves, treedef = jax.tree_util.tree_flatten(delta_tree)
    avals = tuple((tuple(l.shape), str(l.dtype)) for l in leaves)
    return (mesh_fingerprint(mesh), node_shape_key(node), xi, salt,
            avals, str(treedef))


def _exchange_jit(mesh, node):
    """The jitted exchange stage of `node` over `mesh`: XLA module
    `jit_exchange_<node.stable_name()>` (one function per mesh and
    node name, as `fused._jit_step`)."""
    import jax
    from .fused import _named
    name = node.stable_name()
    fn = _EXCH_JIT.get((mesh, name))
    if fn is None:
        def exchange(delta, *, node, xi, salt, bounds, hot_keys, hot_side):
            return exchange_apply(mesh, node, xi, delta, bounds=bounds,
                                  hot_keys=hot_keys, hot_side=hot_side)
        fn = _EXCH_JIT[(mesh, name)] = jax.jit(
            _named(exchange, f"exchange_{name}"),
            static_argnames=("node", "xi", "salt", "bounds", "hot_keys",
                             "hot_side"))
    return fn


def _exch_salt(node, bounds) -> Tuple:
    """Full routing salt of one exchange dispatch: the node's mutable-
    capacity salt plus everything the routing policy can change."""
    return (node._mut_sig(), bounds, node.hot_keys, node.hot_rep_side)


def exchange_delta(mesh, node, xi: int, delta,
                   bounds: Optional[Tuple[int, ...]] = None):
    """Exchange dispatch: a pre-warmed executable when the policy switch
    staged one (zero compile), else the jitted path (cached per mesh;
    static on the node's structural signature + mutable-capacity salt +
    routing policy, so an `exch` growth or a policy change re-traces
    exactly this small program and nothing else). Returns what
    `exchange_apply` does: (routed delta, "exch" stat, "xin" stats)."""
    EXCH_STATS["calls"] += 1
    salt = _exch_salt(node, bounds)
    key = _exch_key(mesh, node, xi, salt, delta)
    compiled = _EXCH_AOT.get(key)
    if compiled is not None:
        EXCH_STATS["aot_hits"] += 1
        return compiled(delta)
    _EXCH_INLINE.add(key)
    return _exchange_jit(mesh, node)(delta, node=node, xi=xi,
                                     salt=node._mut_sig(), bounds=bounds,
                                     hot_keys=node.hot_keys,
                                     hot_side=node.hot_rep_side)


def prewarm_exchange(mesh, node, xi: int, sds_delta,
                     bounds: Optional[Tuple[int, ...]] = None,
                     hot_keys: Tuple[int, ...] = (),
                     hot_rep_side: int = 1) -> None:
    """AOT-compile one exchange stage under a PROSPECTIVE routing policy
    (background work for the checkpoint-time policy switch): lower the
    same trace `exchange_delta` would take, against the avals of the
    last dispatched delta, and park the executable where the post-switch
    dispatch finds it — the compile-service pattern, applied to the one
    program a routing change re-traces."""
    salt = (node._mut_sig(), bounds, tuple(hot_keys), int(hot_rep_side))
    key = _exch_key(mesh, node, xi, salt, sds_delta)
    if key in _EXCH_AOT:
        return
    fn = _exchange_jit(mesh, node)
    lowered = fn.lower(sds_delta, node=node, xi=xi, salt=node._mut_sig(),
                       bounds=bounds, hot_keys=tuple(hot_keys),
                       hot_side=int(hot_rep_side))
    _EXCH_AOT[key] = lowered.compile()


def prune_exchange_aot(mesh, nodes_bounds) -> None:
    """Drop pre-warmed exchange executables superseded by an adopted
    routing policy: for each given (node, bounds), entries keyed by that
    node's SHAPE whose salt differs from the node's CURRENT routing salt
    are dead weight (without this, every policy switch would retain the
    previous policy's compiled executables forever). Shape-keyed, so
    other plans' entries are untouched; a structurally identical twin
    job still on the old policy merely re-traces once (correct, rare)."""
    from .fused import node_shape_key
    meshfp = mesh_fingerprint(mesh)
    live = {}
    for node, bounds in nodes_bounds:
        live.setdefault(node_shape_key(node), set()).add(
            _exch_salt(node, bounds))
    for key in [k for k in _EXCH_AOT
                if k[0] == meshfp and k[1] in live
                and k[3] not in live[k[1]]]:
        del _EXCH_AOT[key]


def exchange_stats() -> dict:
    """Exchange-dispatch accounting (tests assert a policy switch adds
    zero `inline_keys` — no fresh exchange trace at the switch)."""
    return {"inline_keys": len(_EXCH_INLINE),
            "aot_hits": EXCH_STATS["aot_hits"],
            "prewarmed": len(_EXCH_AOT),
            "calls": EXCH_STATS["calls"]}


# ---------------------------------------------------------------------------
# the sharded per-node epoch step
# ---------------------------------------------------------------------------


def sharded_apply(mesh, node, epoch_events: int, state, ins, extra,
                  abstract: bool = False):
    """`Node.apply` over the mesh: shard-local step + in-program stat
    reduction. Source-rooted nodes generate their contiguous slice of
    the epoch's event-id range (`event_lo + shard * epoch_events/n` —
    the pack-time routing of source events to shards); every other node
    consumes its already-owned (or exchange-routed) rows. Stats reduce
    in-program: `psum` for row-flow counters (`Node.stat_sums`), `pmax`
    for capacity needs and violation flags — so the host-side capacity
    lifecycle sees per-shard high-water needs and sizes PER-SHARD
    capacities."""
    import jax
    import jax.numpy as jnp
    from .fused import Delta, MVKeyedNode, _nrows
    n = data_shards(mesh)
    # ceil-div when the cadence does not split evenly: every shard
    # generates the same-size contiguous event-id block (shapes must be
    # uniform across shards) and the PADDED TAIL — ids at or past
    # event_lo + epoch_events, which belong to the NEXT epoch's dispatch
    # — is masked out of the source delta below. Before this, a
    # non-dividing cadence silently degraded the whole job to one chip
    # (the ROADMAP mesh residual).
    ev_local = epoch_events
    pad = 0
    if node.takes_event_lo:
        ev_local = -(-epoch_events // n)
        pad = n * ev_local - epoch_events
    names = node.stat_names
    sums = set(node.stat_sums)
    # per-shard live entries (`Node.enable_shard_live`): the stats named
    # in `live_stats` are high-waters, so their per-shard values are
    # already in the one all_gather below — read before its max
    live_idx = [names.index(s) for s in node.live_stats] \
        if node.shard_live else []

    def local_body(state, ins, extra, abst: bool):
        lst = _drop(state)
        lins = [(_drop(d) if d is not None else None) for d in ins]
        ex = extra
        if node.takes_event_lo and not abst:
            ex = extra + jax.lax.axis_index(SHARD_AXIS).astype(
                jnp.int64) * ev_local
        elif node.takes_feed or isinstance(node, MVKeyedNode):
            # a host-staged ingest feed arrives pre-bucketed per shard
            # (device/ingest.py packs each shard's contiguous event
            # block host-side and device_puts with the vnode-block
            # NamedSharding) — the local step just drops the shard axis
            ex = _drop(extra)
        st, out, stats, aux = node.apply(lst, lins, ex, ev_local)
        if pad and node.takes_event_lo and out is not None \
                and out.pk is not None:
            # drop the tail block's over-generated events (source-rooted
            # deltas carry the event id as pk through Map/Filter chains,
            # so the bound is exact) and recount the flow stat so psum'd
            # rows_out equals the single-chip number
            live = out.mask & (out.pk < extra + epoch_events)
            out = Delta(out.cols, out.sign, live, pk=out.pk, pk2=out.pk2)
            if "rows_out" in names:
                stats = list(stats)
                stats[names.index("rows_out")] = _nrows(live)
        if abst:
            red = list(stats)
            if live_idx:
                red += [sum(stats[i] for i in live_idx)] * n
        else:
            red = [jax.lax.psum(s, SHARD_AXIS) if names[i] in sums
                   else None for i, s in enumerate(stats)]
            mx = [i for i, r in enumerate(red) if r is None]
            if mx:
                # every high-water stat in ONE collective (all_gather +
                # max: see `_pmax`)
                per_shard = jax.lax.all_gather(
                    jnp.stack([stats[i] for i in mx]), SHARD_AXIS)
                hw = jnp.max(per_shard, axis=0)
                for k, i in enumerate(mx):
                    red[i] = hw[k].astype(stats[i].dtype)
            if live_idx:
                live = sum(per_shard[:, mx.index(i)] for i in live_idx)
                red += [live[s] for s in range(n)]
        return st, out, red, aux

    if abstract:
        st, out, red, aux = local_body(state, ins, extra, True)
        lift = lambda t: jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), t)
        return lift(st), lift(out), red, lift(aux)

    def local(state, ins, extra):
        st, out, red, aux = local_body(state, ins, extra, False)
        return _lift1(st), _lift1(out), red, _lift1(aux)

    if node.takes_event_lo:
        from jax.sharding import PartitionSpec as P
        espec = P()
    elif node.takes_feed or isinstance(node, MVKeyedNode):
        espec = _spec_sharded(extra)
    else:
        espec = None
    st_s, out_s, red_s, aux_s = jax.eval_shape(
        lambda s, i_, e: local_body(s, tuple(i_), e, True),
        state, ins, extra)
    out_specs = (_spec_sharded(st_s), _spec_sharded(out_s),
                 _spec_replicated(red_s), _spec_sharded(aux_s))
    fn = _shard_map(local, mesh=mesh,
                    in_specs=(_spec_sharded(state), _spec_sharded(ins),
                              espec),
                    out_specs=out_specs, check_vma=False)
    return fn(state, ins, extra)


_STEP_JIT = {}


def sharded_jit_step(mesh, node):
    """The jitted sharded per-node step, one per mesh and node name (the
    exact analog of fused._jit_step, XLA module `jit_step_<name>` too):
    the compile service AOT-lowers through the SAME function, so inline
    dispatch and background `.lower().compile()` of one signature share
    a trace."""
    import jax
    from .fused import _named
    name = node.stable_name()
    fn = _STEP_JIT.get((mesh, name))
    if fn is None:
        def step(state, ins, extra, *, node, epoch_events, salt):
            return sharded_apply(mesh, node, epoch_events, state, ins,
                                 extra)
        fn = _STEP_JIT[(mesh, name)] = jax.jit(
            _named(step, f"step_{name}"),
            static_argnames=("node", "epoch_events", "salt"))
    return fn


def sharded_node_step(mesh, node, epoch_events: int, state, ins, extra):
    return sharded_jit_step(mesh, node)(state, ins, extra, node=node,
                                        epoch_events=epoch_events,
                                        salt=node._mut_sig())


# ---------------------------------------------------------------------------
# host pull: merge per-shard sorted runs back into the single-chip order
# ---------------------------------------------------------------------------


# serving-tier pull accounting: every host transfer of MV state counts
# here (the read-cache coalescing assertion — "<= 1 device pull per
# (MV, epoch) under a 64-reader storm" — is checked against
# `device_pulls`), and `replica_pulls` records which replica column
# served each one (chip-parallel SELECT serving: reads round-robin over
# replicas, so the write path's replica 0 is not the only chip paying
# host-transfer bandwidth).
PULL_STATS = {"device_pulls": 0, "replica_pulls": {}}
_REPLICA_RR = [0]


def reset_pull_stats() -> None:
    PULL_STATS["device_pulls"] = 0
    PULL_STATS["replica_pulls"] = {}


def _count_pull(rep: int = 0) -> None:
    PULL_STATS["device_pulls"] += 1
    PULL_STATS["replica_pulls"][rep] = \
        PULL_STATS["replica_pulls"].get(rep, 0) + 1
    # mirrored into the metrics registry so the per-replica read-load
    # split is scrapeable (and lands in rw_serving_cache / `risectl
    # serving`), not only a process dict
    from ..utils.metrics import REGISTRY
    REGISTRY.counter(
        "serving_device_pulls_total",
        "host transfers of MV state for SELECT serving").inc()
    REGISTRY.counter(
        "serving_replica_pulls_total",
        "serving-tier device pulls by replica column (read-load "
        "balance over the replica mesh axis)",
        labels=("replica",)).labels(str(rep)).inc()


def replica_device_get(mesh, tree):
    """`jax.device_get` that spreads reads over the replica axis: on a
    replicated 2-D mesh the gathered (fully-replicated) result is
    addressable on every device, so each pull reads its leaves from the
    devices of one replica column, chosen round-robin. On the classic
    1-D mesh this IS `jax.device_get` (plus the pull counter)."""
    import jax
    r = mesh_replicas(mesh) if mesh is not None else 1
    if r <= 1:
        _count_pull(0)
        return jax.device_get(tree)
    rep = _REPLICA_RR[0] % r
    _REPLICA_RR[0] += 1
    _count_pull(rep)
    rep_devices = {d.id for d in mesh.devices[:, rep]}

    def read(leaf):
        shards = getattr(leaf, "addressable_shards", None)
        if shards:
            for s in shards:
                if s.device.id in rep_devices:
                    return np.asarray(s.data)
        return np.asarray(jax.device_get(leaf))

    return jax.tree_util.tree_map(read, tree)


_GATHER_JIT = {}


def _gather_jit(mesh, kind: str, nc: int, m: int):
    """Jitted device-side gather+merge of a sharded terminal-MV state:
    flatten the shard axis, sort live rows to the front IN MERGED KEY
    ORDER (keys/pair identities are globally unique and EMPTY_KEY pads
    sort last), slice to the static live bound `m`, and replicate the
    result — so the host pays ONE device_get per SELECT regardless of
    shard count, instead of a counts round-trip plus per-shard prefix
    fetches."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    key = (mesh_fingerprint(mesh), kind, nc, m)
    fn = _GATHER_JIT.get(key)
    if fn is not None:
        return fn
    rep = NamedSharding(mesh, P())

    if kind == "keyed":
        def gather(st):
            keys = st.keys.reshape(-1)
            order = jnp.argsort(keys)[:m]      # unique keys; pads last
            cols = [st.vals[1 + 2 * i].reshape(-1)[order]
                    for i in range(nc)]
            nulls = [st.vals[2 + 2 * i].reshape(-1)[order]
                     for i in range(nc)]
            return (jnp.sum(st.count), keys[order], cols, nulls)
    else:
        def gather(side):
            from .sorted_state import sort_cols
            jk = side.jk.reshape(-1)
            pk = side.pk.reshape(-1)
            (jks, _pks), vals = sort_cols(
                [jk, pk], [v.reshape(-1) for v in side.vals])
            return (jnp.sum(side.count), [v[:m] for v in vals])

    from .fused import _named
    fn = jax.jit(_named(gather, f"gather_{kind}"), out_shardings=rep)
    _GATHER_JIT[key] = fn
    return fn


def merge_keyed_pull(states, mesh, col_dtypes, live_bound=None):
    """Gather a sharded keyed-MV state merged by ascending packed key —
    keys are globally unique (each lives on its vnode's shard), so the
    merged order IS the 1-shard `mv_rows` order (bit-identity).

    With `live_bound` (caller's high-water live-row estimate, from the
    "needed" stat the sync already pulled), the merge runs IN-PROGRAM:
    device-side sort + compaction + replication, ONE device_get total.
    A stale bound (device holds more live rows than estimated) falls
    back to the two-round-trip host merge — correctness never depends
    on the estimate."""
    import jax
    n = data_shards(mesh)
    nc = len(col_dtypes)
    if live_bound:
        from .capacity import bucket
        cap_total = n * states.keys.shape[1]
        m = min(cap_total, bucket(max(1, int(live_bound)), lo=256))
        total, keys, cols, nulls = replica_device_get(
            mesh, _gather_jit(mesh, "keyed", nc, m)(states))
        total = int(total)
        if total <= m:
            return (np.asarray(keys)[:total],
                    [np.asarray(c)[:total] for c in cols],
                    [np.asarray(u)[:total] for u in nulls])
    _count_pull()
    counts = [int(c) for c in np.asarray(jax.device_get(states.count))]
    # one batched transfer for all shards' live prefixes — per-shard
    # mv_rows pulls would pay n_shards * (1 + 2 * n_cols) host syncs
    # for every SELECT (see merge_pair_pull)
    pulled = jax.device_get(
        [[states.keys[s, :counts[s]]]
         + [states.vals[1 + 2 * i][s, :counts[s]] for i in range(nc)]
         + [states.vals[2 + 2 * i][s, :counts[s]] for i in range(nc)]
         for s in range(n)])
    all_keys = [np.asarray(p[0]) for p in pulled]
    all_cols = [[np.asarray(c) for c in p[1:1 + nc]] for p in pulled]
    all_nulls = [[np.asarray(u) for u in p[1 + nc:]] for p in pulled]
    keys = np.concatenate(all_keys)
    order = np.argsort(keys, kind="stable")
    cols = [np.concatenate([c[i] for c in all_cols])[order]
            for i in range(len(col_dtypes))]
    nulls = [np.concatenate([u[i] for u in all_nulls])[order]
             for i in range(len(col_dtypes))]
    return keys[order], cols, nulls


def merge_pair_pull(side, mesh, live_bound=None):
    """Gather a sharded pair-MV JoinSide: per-shard live prefixes merged
    by (jk, pk) — the sort key of the single-chip sorted multimap, and a
    globally unique pair identity, so the merged order is bit-identical
    to the 1-shard pull. With `live_bound`, the merge runs in-program
    (ONE device_get — see merge_keyed_pull); a stale bound falls back."""
    import jax
    n = data_shards(mesh)
    if live_bound:
        from .capacity import bucket
        cap_total = n * side.jk.shape[1]
        m = min(cap_total, bucket(max(1, int(live_bound)), lo=256))
        total, vals = replica_device_get(
            mesh, _gather_jit(mesh, "pair", len(side.vals), m)(side))
        total = int(total)
        if total <= m:
            return total, [np.asarray(v)[:total] for v in vals]
    # counts first, then per-shard LIVE prefixes only — a grown pair
    # capacity must not make every SELECT transfer n_shards x capacity
    # padded rows for each column
    _count_pull()
    counts = [int(c) for c in np.asarray(jax.device_get(side.count))]
    # one batched transfer for all shards' prefixes — per-slice gets
    # would pay n_shards * (2 + n_cols) host syncs
    # for every SELECT
    pulled = jax.device_get(
        [[side.jk[s, :counts[s]], side.pk[s, :counts[s]]]
         + [v[s, :counts[s]] for v in side.vals] for s in range(n)])
    jks = [np.asarray(p[0]) for p in pulled]
    pks = [np.asarray(p[1]) for p in pulled]
    vals = [[np.asarray(p[2 + i]) for p in pulled]
            for i in range(len(side.vals))]
    jk = np.concatenate(jks)
    pk = np.concatenate(pks)
    order = np.lexsort((pk, jk))
    return (jk[order].shape[0],
            [np.concatenate(v)[order] for v in vals])
