"""Chip benchmark of the PhoneBit serving path: one run of one cell.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (a configuration under a traffic mix) is looked up in
``BENCHMARK.json`` at the root of the checkout.  The run builds the served
program and its inputs from the seed, warms up the cell's own buckets and
frame sizes, measures for ``--seconds``, checks every served answer
against the plain reference, and prints one JSON line last on standard
output: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer ones, read from a
profiler trace of the window), ``device``, ``breakdown`` when traced, and
``checks``, the numbers compared with their limits.

It drives the chip it is started on and refuses to run elsewhere: without
a TPU, or with fewer chips than the cell asks for, it exits non-zero and
prints no result.  JAX's compile cache and the autotune table live under
``.cache/`` inside the checkout, so only a checkout's first run compiles.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def use_checkout_caches(root: pathlib.Path) -> None:
    """JAX's compile cache and the autotune table inside the checkout, at
    fixed paths, whatever the environment names."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(root / ".cache" / "jax")
    os.environ["REPRO_AUTOTUNE_CACHE"] = str(
        root / ".cache" / "repro" / "autotune.json")


def main(argv=None) -> int:
    args = parse(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    use_checkout_caches(ROOT)
    from chipbench import harness

    cell = harness.load_cell(args.workload)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"chipbench: {args.workload} needs {cell.chips} TPU chip(s); "
              f"JAX found {len(devices)} {devices[0].platform!r} device(s)",
              file=sys.stderr)
        return 3
    from repro.cache import configure_compile_cache

    print(f"compile cache: {configure_compile_cache()}")
    print(f"device: {devices[0].device_kind} x{len(devices)}", flush=True)
    line = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                            T_START, log=lambda s: print(s, flush=True))
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
