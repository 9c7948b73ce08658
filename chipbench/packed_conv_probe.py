"""Device time of a cell's packed binary convs, from one probe run: which
node and region scopes run them, the configuration layers each holds,
their milliseconds per image and their share of the roofline.

    python3 chipbench/packed_conv_probe.py --workload <cell> --seed <n> \
        [--seconds 10] [--trace-seconds 2] [--matmul-mode <mode>]

Runs ``scope_probe.probe`` (the cell's server built, warmed, driven and
traced as there) and adds to its JSON line ``packed_conv``: the scopes that
run packed binary convs (per-node direct kernels or fused chain regions;
every binary conv but the first, which reads the uint8 image) with the
layers each holds, their device milliseconds per image served in the
traced tail, the least time of those layers (``costs.least_seconds``) over
those device seconds in percent, and their share of the tail's device-op
seconds; and ``chain_gauges``, the program's chain-region gauges as the
last compiled bucket set them.  ``--matmul-mode`` serves the cell under
another mode (``vpu_chain`` for its regions).  Like ``run.py`` it refuses
to run without a TPU.
"""

from __future__ import annotations

import argparse
import json
import math
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import costs, scopes  # noqa: E402

# How the program lowers a configuration's layers to graph nodes
# (``repro.runtime.graph.lower_packed``, ``passes.fuse_pool_epilogue``):
# node 0 is the image and node 1 the first conv's bit-plane expansion, then
# one node per layer in order until the first float layer.  A pool right
# after a binary conv is fused into the conv's node, which keeps the
# pool's id.  Scopes name nodes by these ids (``n<id>.<op>``,
# ``region.<id>+<id>...``).
FIRST_LAYER_NODE = 2
CHAIN_GAUGES = ("runtime.chain_vmem_bytes", "runtime.chain_hbm_bytes_avoided")


def layer_type(lc: costs.LayerCost) -> str:
    return lc.name.split(":", 1)[1]


def node_layers(layer_costs: list) -> dict[int, tuple[int, ...]]:
    """Graph node id -> indices of the layers it computes, for every
    binary conv and pool before the first float layer."""
    out: dict[int, tuple[int, ...]] = {}
    for i, lc in enumerate(layer_costs):
        t = layer_type(lc)
        if t in ("fconv", "fdense"):
            break
        nid = i + FIRST_LAYER_NODE
        if t == "pool" and i and layer_type(layer_costs[i - 1]) == "bconv":
            out[nid] = out.pop(nid - 1) + (i,)
        elif t in ("bconv", "pool"):
            out[nid] = (i,)
    return out


def scope_nodes(scope: str) -> tuple[int, ...] | None:
    """The graph node ids a node scope (``n<id>.<op>``) or region scope
    (``region.<ids>``) holds, else None."""
    m = scopes._NODE.fullmatch(scope)
    if m:
        return (int(m.group(1)),)
    m = scopes._REGION.fullmatch(scope)
    return tuple(map(int, m.group(1).split("+"))) if m else None


def layers_of_nodes(node_ids, layer_costs: list) -> list[int] | None:
    """Indices of the layers these graph nodes (one node, or the members
    of a region) compute, in order; None when a node is not a binary conv
    or pool of ``layer_costs``."""
    nodes = node_layers(layer_costs)
    if not all(n in nodes for n in node_ids):
        return None
    return sorted(i for n in node_ids for i in nodes[n])


def packed_conv_scopes(per_scope, layer_costs: list) -> dict[str, list[int]]:
    """The scopes whose ops run packed binary convs, with the layers each
    holds: every node or region scope holding a binary conv, except the
    one holding the first layer."""
    out = {}
    for scope in per_scope:
        nodes = scope_nodes(scope)
        layers = layers_of_nodes(nodes, layer_costs) if nodes else None
        if layers and 0 not in layers and any(
                layer_type(layer_costs[i]) == "bconv" for i in layers):
            out[scope] = layers
    return out


def packed_conv(per_scope: dict[str, float], layer_costs: list, peak: dict,
                served: int, calls: int) -> dict | None:
    """The packed convs' scopes, device ms per image, roofline share and
    share of the device-op seconds, for ``served`` images in ``calls``
    executable calls; None where no such scope ran or nothing was
    served."""
    held = packed_conv_scopes(per_scope, layer_costs)
    conv_s = sum(per_scope[s] for s in held)
    if conv_s <= 0 or not served:
        return None
    layers = sorted(i for ls in held.values() for i in ls)
    least = costs.least_seconds([layer_costs[i] for i in layers], peak,
                                images=served, calls=calls)
    return {"scopes": held, "ms_per_image": conv_s / served * 1e3,
            "roofline_pct": 100.0 * least / conv_s,
            "share_pct": 100.0 * conv_s / sum(per_scope.values())}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace-seconds", type=float, default=2.0)
    ap.add_argument("--matmul-mode")
    args = ap.parse_args(argv)
    from chipbench import harness, run, scope_probe

    run.use_checkout_caches(ROOT)
    cell = harness.load_cell(args.workload)
    if args.matmul_mode:
        cell.config = dict(cell.config, matmul_mode=args.matmul_mode)
    import jax

    from repro.obs import metrics

    device = jax.devices()[0]
    if device.platform != "tpu":
        print("packed_conv_probe: needs a TPU", file=sys.stderr)
        return 3
    out = scope_probe.probe(cell, args.seed, args.seconds,
                            args.trace_seconds, host_tracer_level=1,
                            log=lambda s: print(s, flush=True))
    traced = out["traced"]
    layer_costs = costs.layer_costs(cell.config["layers"],
                                    cell.config["input_hw"])
    # the closed loop serves full batches of the largest bucket
    calls = math.ceil(traced["served"] / max(cell.traffic["buckets"]))
    out["matmul_mode"] = cell.config["matmul_mode"]
    out["packed_conv"] = packed_conv(traced["scopes"], layer_costs,
                                     costs.peaks(device.device_kind),
                                     traced["served"], calls)
    snap = metrics.get_registry().snapshot()
    out["chain_gauges"] = {k: snap[k] for k in CHAIN_GAUGES
                           if snap.get(k) is not None}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
