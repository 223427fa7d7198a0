"""Record the small trace that test_trace.py checks the reduction on.

Run on the machine with the chip (it refuses any other platform):
    python benchmarks/tests/record_small_trace.py <out_dir>
writes <out_dir>/small_trace.json: the records `trace.load` gives for a few
jitted sorts under the runner's own span names, with two sleeps between
them that have to come out as idle gaps.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", "lib"))
import trace as trace_lib  # noqa: E402


def main(out_dir):
    import jax
    import jax.numpy as jnp
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("a device trace needs the chip")
    os.makedirs(out_dir, exist_ok=True)
    tdir = os.path.join(out_dir, "small_trace")
    step = jax.jit(lambda x: jnp.sort(x * 3 + 1))
    x = jnp.arange(1 << 20, dtype=jnp.int32)[::-1]
    step(x).block_until_ready()
    trace_lib.start(tdir)
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("window"):
        for k in range(3):
            with jax.profiler.TraceAnnotation(f"tick:{k}"):
                step(x).block_until_ready()
                time.sleep(0.05)
        with jax.profiler.TraceAnnotation("sync"):
            time.sleep(0.1)
            step(x).block_until_ready()
    window_s = time.perf_counter() - t0
    jax.profiler.stop_trace()
    path = trace_lib.find_xplane(tdir)
    records = trace_lib.load(path)
    with open(os.path.join(out_dir, "small_trace.json"), "w") as f:
        json.dump({"window_s": window_s, "records": records}, f)
    with open(os.path.join(out_dir, "small_trace.describe.txt"), "w") as f:
        f.write(trace_lib.describe(path))
    print(json.dumps(trace_lib.reduce(records)), window_s)


if __name__ == "__main__":
    main(sys.argv[1])
