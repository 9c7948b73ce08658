"""The program's readings, the lower end of each limit of ``correct``.

    python3 chipbench/readings.py --workload <cell> --seeds 11,12,13 [--seconds 3]

For each seed, in one process, a whole run of the cell (``harness.run_cell``:
the served program built from the seed, warmed up, driven at the cell's own
load, every distinct served answer checked) with a short window, long
enough to serve every input of the traffic.  Prints one JSON line per seed
with the check's numbers and the largest of each last.  The counterpart of
``control.py``; the benchmark's own runs never run it.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from chipbench import harness, run

    run.use_checkout_caches(ROOT)
    cell = harness.load_cell(args.workload)
    import jax

    if jax.devices()[0].platform != "tpu":
        print("chipbench readings: needs a TPU", file=sys.stderr)
        return 3
    from repro.cache import configure_compile_cache

    configure_compile_cache()
    largest: dict[str, float] = {}
    for seed in (int(s) for s in args.seeds.split(",")):
        logs: list[str] = []
        line = harness.run_cell(cell, seed, args.seconds, False,
                                time.perf_counter(), log=logs.append)
        nums = {k: c["value"] for k, c in line["checks"].items()}
        ties = [s for s in logs if s.startswith("[check] float32 ties")]
        print(json.dumps({"seed": seed, "reading": "program",
                          "correct": line["correct"],
                          "served": line["attempted"] - line["failed"],
                          **nums, "ties": ties}), flush=True)
        for k, v in nums.items():
            largest[k] = max(largest.get(k, v), v)
    print(json.dumps({"workload": args.workload, "largest_sound": largest}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
