"""sha256 (first 16 hex) of the lowered text of every node step of every
cell, at the cell's real sizes, traced from abstract avals on the CPU (no
compile, no run, four virtual devices for a four-shard cell): equal hashes on
two trees are equal programs. Prints `<cell> <i> <node name> <hash>` a step.
usage: python3 tests/lowered_hashes.py [--root TREE] [cell ...]"""
import argparse
import hashlib
import os
import sys

ap = argparse.ArgumentParser()
ap.add_argument("--root", default=os.path.join(os.path.dirname(__file__),
                                               ".."))
ap.add_argument("cells", nargs="*")
args = ap.parse_args()
root = os.path.abspath(args.root)
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
os.environ["JAX_PLATFORMS"] = "cpu"
os.chdir(root)
sys.path[:0] = [root, os.path.join(root, "benchmarks")]
import run as bench_run          # noqa: E402  (puts benchmarks/lib on the path)
import discover                  # noqa: E402
from risingwave_tpu.device.compile_service import (  # noqa: E402
    abstract_program_avals)
from risingwave_tpu.device.fused import _jit_step  # noqa: E402
from risingwave_tpu.device.shard_exec import sharded_jit_step  # noqa: E402

cells = args.cells or [w["name"] for w in discover.load_json(
    os.path.join(root, "BENCHMARK.json"))["workloads"]]
for name in cells:
    cell = discover.Cell(name, root=root,
                         bench_dir=os.path.join(root, "benchmarks"))
    sz = bench_run.sizes(cell, False)
    sz["device"] = {**sz["device"], "aot_compile": False}
    db, job = bench_run.create(cell, sz, 7)
    prog = job.program
    avals = abstract_program_avals(prog.nodes, prog.epoch_events, prog.mesh)
    for i, (node, (st, ins, extra)) in enumerate(zip(prog.nodes, avals)):
        step = _jit_step(node) if prog.mesh is None \
            else sharded_jit_step(prog.mesh, node)
        text = step.lower(st, ins, extra, node=node,
                          epoch_events=prog.epoch_events,
                          salt=node._mut_sig()).as_text()
        print(name, i, prog.node_names[i],
              hashlib.sha256(text.encode()).hexdigest()[:16], flush=True)
    bench_run.drop(db, job)
