"""The control's readings, the upper end of each limit of ``correct``.

    python3 chipbench/control.py --workload <cell> --seeds 11,12,13

For each seed, in one process, the reference computed one precision below
what the configuration states (bfloat16 for its float32) is put in the
program's place: its answers for every input of the seed's traffic are
compared with the float32 reference by the benchmark's own check.  A limit
lies above every sound reading of the program (the ``checks`` of its runs)
and below every reading of the control.  Prints one JSON line per seed and
the smallest reading of each number last.  The benchmark's own runs never
run the control.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]


def control_numbers(cell, seed: int) -> dict:
    """The check's numbers for the bfloat16 reference in the program's
    place, one answer per input of the seed's traffic."""
    import jax.numpy as jnp

    from chipbench import compare, harness, reference

    inputs = harness.generator(cell.traffic["kind"]).make_inputs(
        cell.traffic, cell.config, seed)
    low = reference.Reference(cell.config, seed, dtype=jnp.bfloat16)
    used = list(range(len(inputs["payloads"])))
    raw, _ = low.run(harness.network_inputs(low, cell.traffic, inputs, used))
    served = [{"input": i, "result": compare.answer_rows(cell.config, raw[i])}
              for i in used]
    return harness.check_answers(cell.config, cell.traffic, seed, inputs,
                                 served)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from chipbench import harness, run

    run.use_checkout_caches(ROOT)
    cell = harness.load_cell(args.workload)
    import jax

    if jax.devices()[0].platform != "tpu":
        print("chipbench control: needs a TPU", file=sys.stderr)
        return 3
    from repro.cache import configure_compile_cache

    configure_compile_cache()
    smallest: dict[str, float] = {}
    for seed in (int(s) for s in args.seeds.split(",")):
        nums = control_numbers(cell, seed)
        print(json.dumps({"seed": seed, "reading": "control", **nums}),
              flush=True)
        for k, v in nums.items():
            smallest[k] = min(smallest.get(k, v), v)
    print(json.dumps({"workload": args.workload,
                      "smallest_control": smallest}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
