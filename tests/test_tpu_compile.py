"""Offline compiles for the chip: the only test file that describes a TPU.

The TPU's compiler is installed here and compiles for a chip that is
described, not attached (`v5e:2x2`). These tests lower the fused path's
node step programs — the ones `chip_smoke.py` runs on the real chip — at
the real column widths and dtypes, with the default-on traced features
armed (tier-1 pins them off everywhere else), in the kernel form the chip
path takes (`sorted_state.cheap_compile`, one form on every backend), and
hand them to that compiler. What it refuses here it would refuse on the
chip; nothing runs, so nothing here is a time or a result.

Shapes are the largest power of two that keeps each compile around ten
seconds — compile time is flat in the shape past a few thousand rows
(PR 22 probes, CHANGES.md), so the programs are the bench's programs in
everything but row count.

The topology is described inside a module-scoped fixture, never at
import: only one process may hold the TPU library, and every xdist worker
imports this file.
"""
import jax
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

EPOCH_CHUNK = 16            # fused epoch = 64 * chunk = 1,024 events
CAPACITY = 4096
# the join step is the slowest program to compile (115 s at the shapes
# above): it gets the next rung down, 256-event epochs x 1,024 slots
JOIN_CHUNK, JOIN_CAPACITY = 4, 1024
HBM_BYTES = 16 * 2 ** 30    # one v5e chip

ARMED = ("RW_SKEW_STATS", "RW_FLOW_STATS", "RW_AGG_PRECOMBINE",
         "RW_STATE_TIERING")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_persistent_cache():
    """An offline compile written to the persistent cache cannot be read
    back without a chip (the next one would warn and compile again)."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def armed():
    """Production's configuration: every default-on traced feature on."""
    mp = pytest.MonkeyPatch()
    for k in ARMED:
        mp.setenv(k, "1")
    yield
    mp.undo()


def _plan(mv_sqls, srcs, shards=1, capacity=CAPACITY, chunk=EPOCH_CHUNK):
    """Plan fused MVs through SQL (no epoch runs, no CPU compile) and
    return {node type name: (node index, program)} over all their
    programs — the first node of each type."""
    import bench
    from risingwave_tpu.config import DeviceConfig
    from risingwave_tpu.sql import Database
    db = Database(device=DeviceConfig(capacity=capacity, mesh_shards=shards,
                                      aot_compile=False))
    for src in srcs:
        db.run(getattr(bench, src).format(n=1 << 20, c=chunk))
    out = {}
    for sql in mv_sqls:
        db.run(getattr(bench, sql))
    for job in db._fused.values():
        for i, node in enumerate(job.program.nodes):
            out.setdefault(type(node).__name__, (i, job.program))
    return out


@pytest.fixture(scope="module")
def programs(armed):
    """The smoke's phases, planned at test shapes: the bid group-by
    (datagen chain, pre-combine, agg step, MV apply) and q5 + q7 (hop,
    join)."""
    agg = _plan(["Q4_MV"], ["BID_SRC"])
    q5 = _plan(["Q5_MV"], ["BID_SRC"])
    q7 = _plan(["Q7_MV"], ["BID_SRC"], capacity=JOIN_CAPACITY,
               chunk=JOIN_CHUNK)
    return {"bid_datagen": agg["ChainNode"], "precombine":
            agg["PrecombineNode"], "agg_step": agg["AggNode"],
            "mv_apply": agg["MVKeyedNode"], "hop": q5["HopNode"],
            "join": q7["JoinNode"]}


def _compile_step(idx, program, place, mesh=None):
    """Lower node `idx`'s step exactly as the AOT compile service does
    (`compile_service._compile_task`), against avals placed on the
    described device(s), and compile it with the chip's compiler."""
    from risingwave_tpu.device.compile_service import abstract_program_avals
    from risingwave_tpu.device.fused import _jit_step
    from risingwave_tpu.device.shard_exec import sharded_jit_step
    node = program.nodes[idx]
    sds = abstract_program_avals(program.nodes, program.epoch_events,
                                 mesh)[idx]
    if mesh is None:
        sds = jax.tree_util.tree_map(
            lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=place),
            sds)
    step = _jit_step(node) if mesh is None \
        else sharded_jit_step(mesh, node)
    compiled = step.lower(*sds, node=node,
                          epoch_events=program.epoch_events,
                          salt=node._mut_sig()).compile()
    m = compiled.memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes + m.generated_code_size_in_bytes)
    assert 0 < used < HBM_BYTES
    return compiled


@pytest.mark.parametrize("name", ["bid_datagen", "precombine", "agg_step",
                                  "mv_apply", "hop", "join"])
def test_step_compiles_for_v5e(name, topo, programs, no_persistent_cache):
    idx, program = programs[name]
    _compile_step(idx, program, SingleDeviceSharding(topo.devices[0]))


def test_sharded_step_compiles_for_4_chips(topo, armed,
                                           no_persistent_cache):
    """The mesh path on a Mesh of the four described chips: the agg's
    in-program exchange must lower to an all-to-all over the mesh, and
    its shard_map'd step must compile against mesh-sharded state."""
    import numpy as np
    from risingwave_tpu.device.compile_service import abstract_program_avals
    from risingwave_tpu.device.shard_exec import (_exchange_jit, sds_sharded,
                                                  sharded_apply)
    from risingwave_tpu.parallel.mesh import SHARD_AXIS
    idx, program = _plan(["Q4_MV"], ["BID_SRC"], shards=4)["AggNode"]
    assert program.mesh is not None and program.mesh.devices.size == 4
    mesh = Mesh(np.asarray(topo.devices), (SHARD_AXIS,))
    assert mesh.devices.size == 4
    node, ee = program.nodes[idx], program.epoch_events
    assert node.exch is not None
    # the exchange's input delta = the upstream node's sharded output
    up = node.inputs[node.shard_spec().exchanges[0].input]
    st, ins, extra = abstract_program_avals(program.nodes, ee, mesh)[up]
    _, out, _, _ = jax.eval_shape(
        lambda s, i_, e: sharded_apply(mesh, program.nodes[up], ee, s,
                                       tuple(i_), e, abstract=True),
        st, ins, extra)
    exch = _exchange_jit(mesh, node).lower(
        sds_sharded(out, mesh), node=node, xi=0, salt=node._mut_sig(),
        bounds=None, hot_keys=node.hot_keys,
        hot_side=node.hot_rep_side).compile()
    assert "all-to-all" in exch.as_text()
    _compile_step(idx, program, None, mesh=mesh)
