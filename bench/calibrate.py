"""Readings behind the benchmark's numbers, made on the chip in one
process (the benchmark's own runs do not do this):

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 20 \\
        [--control] [--rates 4,6,8] [--out readings.jsonl]

For every (rate, seed) it runs the cell as ``run.py`` would and prints one
JSON line: the result, the largest served-token gap against the reference
(the lower reading of the correctness limit), with ``--control`` the
largest gap of the float8 control's first choice (the upper reading),
and the queue's growth over the window (``--rates`` overrides an open
loop's rate, for the sweep that finds the knee).
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

import numpy as np  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
# libtpu logs to /tmp/tpu_logs unless told otherwise: keep its logs in the
# run's own temporary directory
os.environ.setdefault("TPU_LOG_DIR",
                      os.path.join(tempfile.gettempdir(), "tpu_logs"))


def _diag(w) -> dict:
    ticks = w.ticks
    third = max(1, len(ticks) // 3)
    steps = {}
    for t in ticks:
        for k, v in t.kinds.items():
            steps[k] = steps.get(k, 0) + v
    recs = [r for r in w.records.values() if r.phase == "window"]
    by_due = sorted(recs, key=lambda r: r.due)
    k = max(1, len(by_due) // 3)
    wait = [((r.first if r.first else w.t_close) - r.due) * 1e3
            for r in by_due]
    plain = [(t.end - t.start) / t.steps for t in ticks
             if t.steps and not t.kinds.get("mixed")]
    mixed = [(t.end - t.start) / t.steps for t in ticks
             if t.steps and t.kinds.get("mixed") == t.steps]
    return {
        "ticks": len(ticks), "steps": steps,
        "plain_step_ms": 1e3 * float(np.median(plain)) if plain else None,
        "mixed_step_ms": 1e3 * float(np.median(mixed)) if mixed else None,
        "tick_ms_median": 1e3 * float(np.median([t.end - t.start
                                                 for t in ticks])),
        "waiting_first_third": float(np.mean([t.waiting
                                              for t in ticks[:third]])),
        "waiting_last_third": float(np.mean([t.waiting
                                             for t in ticks[-third:]])),
        "ttft_ms_median_first_third": float(np.median(wait[:k])),
        "ttft_ms_median_last_third": float(np.median(wait[-k:])),
        "window_requests": len(recs),
        "finished_in_window": sum(1 for r in w.records.values()
                                  if r.finished and r.finished > w.t_open),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", default="")
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import cells, harness

    spec = cells.load_spec(ROOT)
    seeds = [int(s) for s in args.seeds.split(",")]
    rates = [float(r) for r in args.rates.split(",")] if args.rates \
        else [None]
    out = open(args.out, "a") if args.out else None
    first = True
    for rate in rates:
        for seed in seeds:
            cell = cells.resolve(spec, args.workload, ROOT)
            if rate is not None:
                cell.traffic["rate_hz"] = rate
            extra = {"control": args.control}
            t0 = time.perf_counter()
            res = harness.run(cell, seed, args.seconds, False,
                              t_process=T_PROCESS if first else t0,
                              extra=extra)
            line = {"workload": args.workload, "seed": seed,
                    "rate_hz": cell.traffic.get("rate_hz"),
                    "result": res, "diag": _diag(extra["window"]),
                    "wall_s": time.perf_counter() - t0}
            g = extra["served"]
            line["gap"] = {"max": float(g.max()) if g.size else None,
                           "p99": float(np.percentile(g, 99)) if g.size
                           else None, "n": int(g.size),
                           "nonzero": int((g > 0).sum())}
            if args.control:
                c = extra["control"]
                line["control_gap"] = {
                    "max": float(c.max()) if c.size else None,
                    "p99": float(np.percentile(c, 99)) if c.size else None,
                    "nonzero": int((c > 0).sum())}
            text = json.dumps(line)
            print(text, flush=True)
            if out:
                out.write(text + "\n")
                out.flush()
            first = False
    return 0


if __name__ == "__main__":
    sys.exit(main())
