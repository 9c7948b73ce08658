"""Benchmark entry point: ``PYTHONPATH=src python -m benchmarks.run``.

One harness per paper table/figure, the per-kernel microbench (which
writes the machine-readable ``BENCH_kernels.json`` perf artifact), and the
roofline reader (which consumes cached dry-run artifacts if present).
Each harness prints a CSV block.

Exits non-zero when any harness raised (after running the rest).

``--smoke`` runs CI-sized shapes — a fast regression tripwire that still
writes the BENCH artifacts; ``--only {kernels,serving,workloads,
endurance,coldstart}`` restricts the run to one suite (and composes with
``--smoke``: ``--smoke --only serving`` is the serving tripwire alone).
"""

from __future__ import annotations

import argparse
import pathlib
import traceback

#: ``--only`` choices: each names one suite; the callable gets
#: ``smoke=`` so ``--smoke --only X`` runs X's CI-sized variant.
ONLY_SUITES = ("kernels", "serving", "workloads", "endurance", "coldstart")


def _suite_runner(only: str):
    from benchmarks import (coldstart_bench, endurance_bench,
                            kernels_bench, serving_bench, workloads_bench)

    return {
        "kernels": kernels_bench.run,
        "serving": serving_bench.run,
        "workloads": workloads_bench.run,
        "endurance": endurance_bench.run,
        "coldstart": coldstart_bench.run,
    }[only]


def main(argv: list[str] | None = None) -> None:
    parser = argparse.ArgumentParser(prog="benchmarks.run")
    parser.add_argument("--smoke", action="store_true",
                        help="CI-sized shapes (fast tripwire; still "
                             "writes the BENCH_*.json artifacts)")
    parser.add_argument("--only", choices=ONLY_SUITES, default=None,
                        help="run one suite instead of everything; "
                             "composes with --smoke")
    args = parser.parse_args(argv)
    from repro.cache import configure_compile_cache

    configure_compile_cache()

    if args.only is not None:
        _suite_runner(args.only)(smoke=args.smoke)
        return

    from benchmarks import (coldstart_bench, crossover, endurance_bench,
                            fig5_layers, graph_plan, kernels_bench,
                            roofline, serving_bench, table2_model_size,
                            table3_runtime, table4_energy,
                            workloads_bench)

    if args.smoke:
        kernels_bench.run(smoke=True)
        workloads_bench.run(smoke=True)
        return

    t3_rows = None
    failed: list[str] = []
    for name, fn in (
            ("table2_model_size", table2_model_size.run),
            ("table3_runtime", table3_runtime.run),
            ("fig5_layers", fig5_layers.run),
            ("graph_plan", graph_plan.run),
            ("kernels_bench", kernels_bench.run),
            ("serving_bench", serving_bench.run),
            ("endurance_bench", endurance_bench.run),
            ("coldstart_bench", coldstart_bench.run),
            ("workloads_bench", workloads_bench.run),
            ("crossover", crossover.run),
    ):
        try:
            out = fn()
            if name == "table3_runtime":
                t3_rows = out
        except Exception:
            print(f"!! {name} failed:")
            traceback.print_exc()
            failed.append(name)

    try:
        table4_energy.run(t3_rows)
    except Exception:
        print("!! table4_energy failed:")
        traceback.print_exc()
        failed.append("table4_energy")

    if pathlib.Path("artifacts/dryrun").exists():
        try:
            roofline.run()
        except Exception:
            print("!! roofline failed:")
            traceback.print_exc()
            failed.append("roofline")
    else:
        print("# §Roofline: no artifacts/dryrun cache — run "
              "`python -m repro.launch.dryrun --all` first")
    if failed:
        raise SystemExit(f"benchmark phases failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
