"""Serving benchmark: run one cell of ``BENCHMARK.json`` on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout of the repository, on a machine whose
chips JAX can see. Prints progress and, as its last lines, each number the
correctness check compared beside its limit on standard error; prints one
JSON object as the last line of standard output:

    {"correct", "attempted", "failed", "metrics", "device",
     ["breakdown",] "checks"}

With ``--trace 0`` the metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics. Exits non-zero, with no result, when
JAX finds no TPU or fewer chips than the cell needs, or when the program
it measures (``src/``) is not beside it.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
# libtpu logs to /tmp/tpu_logs unless told otherwise: keep its logs in the
# run's own temporary directory
os.environ.setdefault("TPU_LOG_DIR",
                      os.path.join(tempfile.gettempdir(), "tpu_logs"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")
    if not (ROOT / "src" / "repro").is_dir():
        print(f"error: the program under test (src/repro) is not in "
              f"{ROOT}", file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import cells, device, harness

    cell = cells.resolve(cells.load_spec(ROOT), args.workload, ROOT)
    try:
        result = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                             t_process=T_PROCESS)
    except device.NoDevice as e:
        print(f"error: {e}", file=sys.stderr)
        return 3
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
