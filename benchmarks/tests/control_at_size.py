"""The control of `correct` at a cell's own size (numpy only, no device):

    python benchmarks/tests/control_at_size.py <cell> <seed> [<seed> ...]

For each seed: the reference with the configuration's exactly-once guarantee
broken (`control`: the last epoch applied twice) is compared with the
reference as a run compares the MV; it has to differ. PERF.md holds the
readings the limits were set from.
"""
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "lib"))

import discover  # noqa: E402
import run  # noqa: E402


def main(workload, seeds):
    cell = discover.Cell(workload)
    sz = run.sizes(cell, rehearse=False)
    code = cell.config_code
    for seed in seeds:
        want = code.reference(seed, sz["events"])
        missing, unexpected = run.multiset_diff(
            code.control(seed, sz["events"], sz["epoch_events"]), want)
        print(json.dumps({"cell": workload, "seed": seed,
                          "reference_rows": len(want),
                          "control_rows_missing": missing,
                          "control_rows_unexpected": unexpected,
                          "control_correct": missing + unexpected == 0}),
              flush=True)
        if missing + unexpected == 0:
            raise SystemExit("the control came out correct")


if __name__ == "__main__":
    main(sys.argv[1], [int(s) for s in sys.argv[2:]])
