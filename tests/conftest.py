"""Test configuration: force an 8-virtual-device CPU platform so multi-chip
sharding paths are exercised without TPU hardware (real chips are reached
only by `chip_smoke.py`, through the builder's chip tool).

Tests run on the CPU whatever the machine holds: the platform is pinned by
env var and by jax config, before any backend is initialized.
"""
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
# Key-skew telemetry (device/skew_stats.py) extends every keyed node's
# traced step; on the CPU test platform that extra XLA compile across
# dozens of fused-path tests costs real wall the tier-1 budget doesn't
# have. Pin it OFF suite-wide; the dedicated skew tests
# (test_observability2.py) force it back on per test.
os.environ.setdefault("RW_SKEW_STATS", "0")
# Flow telemetry (traffic-per-vnode histograms) rides the same traced
# step and costs the same extra CPU-platform compile; pinned OFF
# suite-wide, forced on per test by tests/test_flow_telemetry.py.
# Production default stays ON (DeviceConfig.flow_stats).
os.environ.setdefault("RW_FLOW_STATS", "0")
# Same budget call for the agg pre-combine stage (an extra traced
# program per fused agg): pinned OFF suite-wide, forced on per test by
# the dedicated skew-defense tests (test_skew_ops.py). Production
# default stays ON (DeviceConfig.agg_precombine).
os.environ.setdefault("RW_AGG_PRECOMBINE", "0")
# And for the hot/cold state tier (a touch column in every keyed step
# plus promote/evict surgery programs): pinned OFF suite-wide, forced
# on per test by the dedicated tiering tests (test_tiering.py).
# Production default stays ON (DeviceConfig.state_tiering).
os.environ.setdefault("RW_STATE_TIERING", "0")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest

MESH_DEVICES = 8


def pytest_collection_modifyitems(config, items):
    """`mesh`-marked tests need the 8 virtual CPU devices forced above;
    if jax initialized before the XLA flag landed (or the platform
    overrode it), skip them instead of failing on make_mesh."""
    if len(jax.devices()) >= MESH_DEVICES:
        return
    skip = pytest.mark.skip(reason=f"needs {MESH_DEVICES} devices, have "
                                   f"{len(jax.devices())} (XLA_FLAGS="
                                   "--xla_force_host_platform_device_"
                                   "count did not take)")
    for item in items:
        if "mesh" in item.keywords:
            item.add_marker(skip)


def _eqns(jaxpr, stack=""):
    """Every equation of a jaxpr, sub-jaxprs included, each with the
    named-scope path it sits under (an equation's name stack is relative
    to the equation that holds its jaxpr)."""
    from jax.extend import core as jcore
    for eqn in jaxpr.eqns:
        at = f"{stack}/{eqn.source_info.name_stack}"
        yield eqn, at
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                sub = getattr(sub, "jaxpr", sub)
                if isinstance(sub, jcore.Jaxpr):
                    yield from _eqns(sub, at)


def _loops(jaxpr):
    """Every `scan` / `while` equation of a jaxpr, sub-jaxprs included."""
    return [eqn for eqn, _at in _eqns(jaxpr)
            if eqn.primitive.name in ("scan", "while")]


def _loops_over(jaxpr, rows):
    """The loops of a jaxpr that read, carry or produce an array with a
    dimension of `rows` or more: [(primitive, [shape...])]. A binary
    search over a state (`searchsorted` of the compile-cheap form is a
    loop that reads the sorted run) shows here whatever its query count."""
    out = []
    for eqn in _loops(jaxpr):
        long = [v.aval.shape for v in list(eqn.invars) + list(eqn.outvars)
                if max(getattr(v.aval, "shape", ()) or (0,)) >= rows]
        if long:
            out.append((eqn.primitive.name, long))
    return out


def _prims_under(jaxpr, scope):
    """The primitives of a jaxpr's equations that sit under the
    `jax.named_scope` `scope`, sub-jaxprs included."""
    return {eqn.primitive.name for eqn, at in _eqns(jaxpr)
            if scope in at.split("/")}


@pytest.fixture
def jaxpr_prims_under():
    """prims_under(jaxpr, scope) — which primitives a stage of a step
    program holds (tests/test_device_state.py, tests/test_mesh_bid_agg.py):
    the stage is the named scope the device trace shows it under."""
    return _prims_under


@pytest.fixture
def jaxpr_loops():
    """(loops, loops_over) — the probes the structural tests of the step
    programs share (tests/test_tiering.py, tests/test_mesh_bid_agg.py,
    tests/test_device_state.py)."""
    return _loops, _loops_over


@pytest.fixture
def mesh8():
    """The 8-shard 1-D device mesh the sharded fused path runs over in
    tier-1: the default platform's 8 virtual CPU devices forced above
    (`parallel/mesh.make_mesh` raises where there are fewer)."""
    from risingwave_tpu.parallel.mesh import make_mesh
    return make_mesh(MESH_DEVICES)


def pytest_sessionfinish(session, exitstatus):
    """Session-end guards.

    1. AOT thread join: fused tests leave background compile-service
       workers (and queued compiles) behind; join them so no compile
       lands mid-teardown and no leaked thread flakes a later plugin
       (the threads are daemons, but a compile finishing during
       interpreter shutdown can die inside jax with a noisy traceback).
    2. CI metrics-naming lint: after the suite has exercised every code
       path that registers metrics, walk the process-global REGISTRY and
       fail the run on Prometheus-invalid metric/label names or on a
       name registered with conflicting label sets
       (utils/metrics.lint_registry).

    A collection-only run (no tests executed) has nothing to guard."""
    if getattr(session, "testscollected", 0) == 0:
        return
    try:
        from risingwave_tpu.device.compile_service import shutdown
        shutdown(join=True, timeout=60.0)
    except ImportError:
        pass
    try:
        from risingwave_tpu.device.fused import join_prewarm_threads
        join_prewarm_threads(timeout=30.0)
    except ImportError:
        pass
    from risingwave_tpu.utils.metrics import (REGISTRY, dead_telemetry,
                                              lint_registry)
    rep = session.config.pluginmanager.get_plugin("terminalreporter")

    def _say(msg, red):
        if rep is not None:
            rep.write_line(msg, red=red, yellow=not red)
        else:
            print(msg)

    problems = lint_registry(REGISTRY)
    if problems:
        for p in problems:
            _say(f"metrics lint: {p}", red=True)
        session.exitstatus = 1
    # advisory only: a labeled family no test ever touched is either dead
    # plumbing or just outside this run's subset — warn, don't fail
    for d in dead_telemetry(REGISTRY):
        _say(f"metrics lint (warn): {d}", red=False)
