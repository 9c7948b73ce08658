"""Graph-runtime plan report: per-layer backend winners + arena memory plan.

The Fig-5-style layer breakdown, engine edition: lower YOLOv2-Tiny through
the graph runtime, autotune every dispatchable node (which backend wins
*where* — popcount vs ±1-matmul is shape-dependent, see the crossover
harness), and emit

* one row per dispatchable node: shape, winning backend, candidate timings;
* the static memory plan: per-buffer arena offsets, peak vs naive bytes —
  the §VI memory-bandwidth discipline as a planned number instead of a
  hope.

Input resolution is scaled 1/4 (as fig5_layers does) to keep host timings
tractable; channel dims are exact.
"""

from __future__ import annotations

import functools

import jax

from benchmarks.common import emit
from repro.core import bnn_model, converter
from repro.models import paper_nets
from repro.runtime import (Autotuner, chain_report, fuse_pool_epilogue,
                           infer_types, lower_packed, partition_chains,
                           plan_memory)
from repro.runtime.autotune import _node_signature

_HW = 104  # 416 / 4
_BATCH = 1


@functools.lru_cache(maxsize=None)
def _tuned(net: str):
    """(graph, types, tuner, choices) for ``net`` at the scaled resolution;
    cached so fig5_layers and run() share one tuning sweep."""
    spec, _ = paper_nets.get(net)
    params = bnn_model.init_params(jax.random.key(0), spec)
    packed = converter.convert(params, spec, (_HW, _HW))
    # The serving graph: conv+pool pairs fused (engine applies the same
    # pass), so winners/arena rows match what the engine executes.
    graph = fuse_pool_epilogue(lower_packed(spec, packed, (_HW, _HW)))
    in_shape = (_BATCH, _HW, _HW, spec[0].c_in)
    types = infer_types(graph, in_shape)
    # persist=False: report *this* run's measurements, never warm-start
    # stale winners from <repo>/.cache/repro/autotune.json.
    tuner = Autotuner(candidates=("xla", "xla_pm1"), warmup=1, iters=2,
                      persist=False)
    choices = tuner.tune(graph, in_shape)
    return graph, in_shape, types, tuner, choices


def conv_winners(net: str = "yolov2-tiny") -> list[str]:
    """Winning backend per dispatchable conv/dense node, in topo order —
    what fig5_layers joins onto its per-layer breakdown."""
    graph, _, _, _, choices = _tuned(net)
    return [choices[nid] for nid in graph.topo_order() if nid in choices]


def run(net: str = "yolov2-tiny") -> list[dict]:
    graph, in_shape, types, tuner, choices = _tuned(net)

    rows = []
    for nid in graph.topo_order():
        node = graph.nodes[nid]
        if nid not in choices:
            continue
        t = types[nid]
        entry = tuner.cache[_node_signature(
            node, types[node.inputs[0]].shape, tuner.candidates)]
        row = dict(node=nid, op=node.op,
                   out_shape="x".join(map(str, t.shape)),
                   channels=node.attrs.get("channels"),
                   backend=choices[nid])
        for b, ms in entry["timings_ms"].items():
            row[f"{b}_ms"] = ms
        rows.append(row)
    emit(rows, f"Graph plan — per-node backend winners, {net} "
               f"@{_HW}x{_HW} (host)")

    plan = plan_memory(graph, in_shape, types=types)
    mem_rows = plan.report()
    emit(mem_rows, f"Graph plan — arena assignment, {net} "
                   f"(peak {plan.peak_bytes()} B vs naive "
                   f"{plan.naive_bytes()} B, "
                   f"{plan.naive_bytes() / max(plan.peak_bytes(), 1):.2f}x "
                   f"reuse)")

    # Chain-fusion regions (DESIGN.md §9): which runs fuse into single
    # megakernel calls, their VMEM arena plans, and the HBM boundary
    # traffic each region removes vs the per-node path.
    chains = partition_chains(graph, in_shape, types=types)
    region_rows = chain_report(chains)
    total_avoided = sum(r["hbm_bytes_avoided"] for r in region_rows)
    emit(region_rows, f"Graph plan — megakernel regions, {net} "
                      f"({len(region_rows)} chains, {total_avoided} HBM "
                      f"bytes avoided per forward)")
    return rows


if __name__ == "__main__":
    run()
