"""Multi-device placement on the runtime IR (DESIGN.md §13).

The placement pass maps one serving graph onto several devices.  Two
placement kinds share the abstraction (duck-typed on ``.kind`` so the
serving layer never imports this module at import time):

* **data-parallel** — the batch dim is sharded over a mesh axis; the
  graph itself is untouched (one executable, ``NamedSharding`` inputs).
  The concrete placement object lives in
  :mod:`repro.distributed.sharding` (``DataParallel``).
* **pipeline-parallel** — the *schedule* is cut into contiguous stages,
  each compiled into its own per-device executable, with explicit
  cross-stage transfer steps between them (``Pipelined`` in
  :mod:`repro.distributed.pipeline`).

Pipeline cuts are only legal at the graph's **HBM touch points**: a
schedule position where exactly one live value crosses the cut (the
boundary tensor).  Chain regions (DESIGN.md §9) keep their interiors in
VMEM, so when the serving mode is ``vpu_chain`` the pass additionally
refuses to cut inside a chain — stage boundaries then coincide with
region boundaries, which were already the only activations reaching
HBM.  Cut positions are chosen by a small DP that minimizes the
heaviest stage under a static per-node cost model (xor-popcount MAC
count for conv/dense, output bytes otherwise) — the pipeline's
steady-state throughput is gated by its slowest stage.

:class:`StagedExecutor` is the executor half: one
:class:`~repro.runtime.executor.GraphExecutor` per stage, its params
committed to the stage's device, with a ``jax.device_put`` transfer
moving the boundary tensor to the next stage's device.  Dispatch stays
async end to end — each stage's work is enqueued on its own device and
the transfer is itself async — so under the server's double-buffered
dispatch, batch *k+1* occupies stage 0 while batch *k* is still in
stage 1: the classic pipeline overlap, with no bespoke scheduler.  All
stage executables are bit-exact with the single-device graph (stage
boundaries are exact tensor handoffs), so placement — like backend
choice — is purely a performance/capacity decision.

``trace_count`` sums over stages, preserving the serve-time
no-recompile contract end to end.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Mapping, Sequence

import jax

from repro.core.binary_conv import conv_out_size
from repro.runtime.graph import Graph, Node, TensorType, infer_types

_CONV_OPS = ("packed_conv", "packed_conv_pool", "conv_counts")


# ---------------------------------------------------------------------------
# Cost model
# ---------------------------------------------------------------------------

def node_cost(node: Node, types: Mapping[int, TensorType]) -> float:
    """Static work estimate for one node (relative units).

    Conv/dense ops: xor-popcount MACs — output positions × kernel area ×
    input words.  Everything else: output bytes (layout shuffles and
    pools are bandwidth-bound).  Only *relative* stage balance matters,
    so a crude model is enough; the forced-mesh bench rows measure the
    real split.
    """
    t = types[node.id]
    a = node.attrs
    if node.op in _CONV_OPS:
        # Pre-pool conv dims: packed_conv_pool's output type is the
        # *pooled* map, but the xor-popcount work happens at conv size.
        in_t = types[node.inputs[0]]
        oh = conv_out_size(in_t.shape[1], a["kernel"], a["stride"],
                           a["pad"])
        ow = conv_out_size(in_t.shape[2], a["kernel"], a["stride"],
                           a["pad"])
        return float(oh * ow * a["kernel"] * a["kernel"] * in_t.shape[-1]
                     * a["channels"] * t.shape[0])
    if node.op in ("packed_dense", "dense_counts", "float_dense"):
        in_t = types[node.inputs[0]]
        k = 1
        for d in in_t.shape[1:]:
            k *= d
        return float(k * a["channels"] * t.shape[0])
    if node.op == "float_conv":
        in_t = types[node.inputs[0]]
        return float(t.shape[1] * t.shape[2] * a["kernel"] * a["kernel"]
                     * in_t.shape[-1] * a["channels"] * t.shape[0])
    return float(t.nbytes)


# ---------------------------------------------------------------------------
# Cut candidates: the schedule's HBM touch points
# ---------------------------------------------------------------------------

def cut_candidates(graph: Graph,
                   forbidden: frozenset[int] | set[int] = frozenset()
                   ) -> list[tuple[int, int]]:
    """Legal pipeline cut positions as ``(schedule_index, boundary_id)``.

    A cut after ``schedule[i]`` is legal when exactly one live value
    crosses it — that value is the stage-boundary tensor the transfer
    step will ship.  ``forbidden`` node ids (chain-region interiors)
    disqualify a position when the boundary or the next node sits inside
    a fused region.
    """
    schedule = graph.topo_order()
    pos = {nid: i for i, nid in enumerate(schedule)}
    cons = graph.consumers()
    out: list[tuple[int, int]] = []
    for i in range(len(schedule) - 1):
        live = [nid for nid in schedule[:i + 1]
                if any(pos[c] > i for c in cons[nid])
                or (nid == graph.output_id)]
        if len(live) != 1:
            continue
        boundary = live[0]
        if boundary in forbidden or schedule[i + 1] in forbidden:
            continue
        out.append((i, boundary))
    return out


def chain_interiors(chains: Sequence[Any]) -> frozenset[int]:
    """Node ids strictly inside a chain region (every member but the
    tail): cutting there would split an activation that never reaches
    HBM.  Chain *tails* stay legal boundaries — they are exactly the
    region boundaries DESIGN.md §9 identifies as the HBM touch points."""
    ids: set[int] = set()
    for c in chains:
        ids.update(c.node_ids[:-1])
    return frozenset(ids)


# ---------------------------------------------------------------------------
# Stage planning
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class StagePlan:
    """A pipeline partition of one graph's schedule.

    ``stages``     node ids per stage, schedule order, contiguous;
    ``boundaries`` the producer node id shipped across each cut
                   (``len == len(stages) - 1``);
    ``costs``      static cost-model total per stage.
    """
    stages: tuple[tuple[int, ...], ...]
    boundaries: tuple[int, ...]
    costs: tuple[float, ...]

    @property
    def n_stages(self) -> int:
        return len(self.stages)

    def report(self) -> list[dict]:
        total = sum(self.costs) or 1.0
        rows = []
        for i, (ids, cost) in enumerate(zip(self.stages, self.costs)):
            rows.append(dict(
                stage=i, nodes=list(ids), cost=cost,
                share=round(cost / total, 4),
                boundary=(self.boundaries[i]
                          if i < len(self.boundaries) else None)))
        return rows


def plan_pipeline(graph: Graph, input_shape: Sequence[int],
                  n_stages: int, *,
                  forbidden: frozenset[int] | set[int] = frozenset(),
                  types: Mapping[int, TensorType] | None = None
                  ) -> StagePlan:
    """Cut the schedule into ≤ ``n_stages`` cost-balanced stages.

    Chooses cut positions among :func:`cut_candidates` minimizing the
    maximum stage cost (pipeline throughput is gated by the slowest
    stage) via DP.  When the graph offers fewer legal cuts than
    requested stages, the plan degrades to what is legal — callers get
    ``plan.n_stages`` back, not an error.
    """
    graph.validate()
    if n_stages < 1:
        raise ValueError(f"n_stages must be >= 1, got {n_stages}")
    types = types if types is not None else infer_types(
        graph, tuple(input_shape))
    schedule = graph.topo_order()
    costs = [node_cost(graph.nodes[nid], types) for nid in schedule]
    cands = cut_candidates(graph, forbidden)
    # A boundary must be produced by the stage immediately before its
    # cut; a value crossing an *entire* stage would leave that stage
    # output-less.  Cuts are chosen left to right, so it is enough to
    # drop candidate pairs that would sandwich a stage with no cost —
    # the DP below never selects two cuts at the same position anyway.
    k = min(n_stages - 1, len(cands))
    if k == 0:
        return StagePlan((tuple(schedule),), (), (sum(costs),))

    # prefix[i] = cost of schedule[0..i-1]
    prefix = [0.0]
    for c in costs:
        prefix.append(prefix[-1] + c)

    def seg(a: int, b: int) -> float:
        """Cost of schedule[a..b] inclusive."""
        return prefix[b + 1] - prefix[a]

    n = len(schedule)
    positions = [p for p, _ in cands]
    # best[j][ci]: minimal max-stage-cost using j cuts, the last at
    # candidate index ci.  O(k · |cands|²) — graphs are tens of nodes.
    best = [[float("inf")] * len(positions) for _ in range(k + 1)]
    back = [[-1] * len(positions) for _ in range(k + 1)]
    for ci, p in enumerate(positions):
        best[1][ci] = seg(0, p)
    for j in range(2, k + 1):
        for ci, p in enumerate(positions):
            for pi in range(ci):
                if positions[pi] >= p:
                    continue
                cand = max(best[j - 1][pi], seg(positions[pi] + 1, p))
                if cand < best[j][ci]:
                    best[j][ci] = cand
                    back[j][ci] = pi
    # close with the tail stage
    final_best, final_ci = float("inf"), -1
    for ci, p in enumerate(positions):
        cand = max(best[k][ci], seg(p + 1, n - 1))
        if cand < final_best:
            final_best, final_ci = cand, ci
    chosen: list[int] = []
    j, ci = k, final_ci
    while j >= 1 and ci >= 0:
        chosen.append(ci)
        ci = back[j][ci]
        j -= 1
    chosen.reverse()
    cut_pos = [positions[c] for c in chosen]
    boundary = {p: b for p, b in cands}

    stages: list[tuple[int, ...]] = []
    stage_costs: list[float] = []
    start = 0
    for p in cut_pos + [n - 1]:
        stages.append(tuple(schedule[start:p + 1]))
        stage_costs.append(seg(start, p))
        start = p + 1
    boundaries = tuple(boundary[p] for p in cut_pos)
    for ids, b in zip(stages, boundaries):
        assert b in ids, (b, ids)   # boundary produced by its own stage
    return StagePlan(tuple(stages), boundaries, tuple(stage_costs))


# ---------------------------------------------------------------------------
# Stage subgraphs
# ---------------------------------------------------------------------------

def stage_subgraph(graph: Graph, node_ids: Sequence[int],
                   boundary_in: int | None,
                   device=None) -> Graph:
    """One stage as a self-contained Graph.

    ``boundary_in`` (the previous stage's boundary producer) is replaced
    by an ``input`` placeholder *keeping its node id*, so every
    intra-stage edge survives untouched.  Stage 0 passes ``None`` and
    keeps the original graph input.  When ``device`` is given, node
    params are committed there — jit then compiles and runs the stage on
    that device (committed-operand placement, no deprecated
    ``jit(device=)``).
    """
    g = Graph(input_hw=graph.input_hw)
    if boundary_in is not None:
        src = graph.nodes[boundary_in]
        g.nodes[boundary_in] = Node(
            boundary_in, "input", (),
            attrs=dict(channels=src.attrs.get("channels")))
        g.input_id = boundary_in
    for nid in node_ids:
        n = graph.nodes[nid]
        params = dict(n.params)
        if device is not None and params:
            params = jax.tree.map(lambda a: jax.device_put(a, device),
                                  params)
        g.nodes[nid] = Node(nid, n.op, n.inputs, dict(n.attrs), params)
        if n.op == "input":
            g.input_id = nid
    g.output_id = node_ids[-1]
    g.validate()
    return g


# ---------------------------------------------------------------------------
# Staged (pipeline-parallel) executor
# ---------------------------------------------------------------------------

class StagedExecutor:
    """Per-stage executables with cross-stage transfers (DESIGN.md §13).

    Presents the :class:`GraphExecutor` serve surface — ``__call__``,
    ``trace_count``, ``backend_report`` — so the engine's per-bucket
    executable cache and the server dispatch path work unchanged.  Each
    call walks the stages: move the boundary tensor to the stage's
    device (async transfer), invoke the stage executable (async
    dispatch).  The caller blocks only at the final readback, exactly as
    on one device.
    """

    def __init__(self, graph: Graph, input_shape: Sequence[int],
                 devices: Sequence[Any], *, mode: str = "xla",
                 tuner=None, donate_input: bool = False,
                 vmem_budget: int | None = None):
        from repro.runtime import regions as _regions
        from repro.runtime.executor import GraphExecutor

        if not devices:
            raise ValueError("pipeline placement needs >= 1 device")
        self.graph = graph
        self.mode = mode
        types = infer_types(graph, tuple(input_shape))
        forbidden: frozenset[int] = frozenset()
        if mode == "vpu_chain":
            budget = (vmem_budget if vmem_budget is not None
                      else _regions.VMEM_BUDGET)
            forbidden = chain_interiors(_regions.partition_chains(
                graph, tuple(input_shape), vmem_budget=budget))
        self.plan = plan_pipeline(graph, input_shape, len(devices),
                                  forbidden=forbidden, types=types)
        self.devices = tuple(devices[:self.plan.n_stages])
        self._stage_exes = []
        shape = tuple(input_shape)
        for i, ids in enumerate(self.plan.stages):
            boundary_in = (self.plan.boundaries[i - 1] if i else None)
            sub = stage_subgraph(graph, ids, boundary_in,
                                 device=self.devices[i])
            if mode == "vpu_chain":
                exe = _regions.chain_executor(
                    sub, shape, tuner=tuner, donate_input=donate_input,
                    **({"vmem_budget": vmem_budget}
                       if vmem_budget is not None else {}))
            elif mode == "auto":
                if tuner is None:
                    raise ValueError("mode='auto' needs a tuner")
                exe = tuner.tuned_executor(sub, shape,
                                           donate_input=donate_input)
            else:
                exe = GraphExecutor(sub, mode, donate_input=donate_input)
            self._stage_exes.append(exe)
            if i < len(self.plan.boundaries):
                shape = types[self.plan.boundaries[i]].shape

    # ---- serve surface ---------------------------------------------------
    def __call__(self, x):
        for dev, exe in zip(self.devices, self._stage_exes):
            x = jax.device_put(x, dev)     # cross-stage transfer (async)
            x = exe(x)
        return x

    @property
    def trace_count(self) -> int:
        return sum(e.trace_count for e in self._stage_exes)

    @property
    def regions(self) -> tuple:
        return tuple(r for e in self._stage_exes
                     for r in getattr(e, "regions", ()))

    @property
    def stage_executors(self) -> tuple:
        return tuple(self._stage_exes)

    def backend_report(self) -> list[dict]:
        rows: list[dict] = []
        for i, (dev, exe) in enumerate(zip(self.devices,
                                           self._stage_exes)):
            for row in exe.backend_report():
                rows.append(dict(row, stage=i, device=str(dev)))
        return rows

    def stage_report(self) -> list[dict]:
        """The placement decision, one row per stage: nodes, static cost
        share, assigned device, boundary tensor shipped downstream."""
        rows = self.plan.report()
        for row, dev in zip(rows, self.devices):
            row["device"] = str(dev)
        return rows


def staged_executor(graph: Graph, input_shape: Sequence[int],
                    devices: Sequence[Any], *, mode: str = "xla",
                    tuner=None, donate_input: bool = False,
                    vmem_budget: int | None = None) -> StagedExecutor:
    """Build the pipeline-parallel executor for ``graph`` over
    ``devices`` (the engine's ``compile(pipeline=...)`` entry point)."""
    return StagedExecutor(graph, input_shape, devices, mode=mode,
                          tuner=tuner, donate_input=donate_input,
                          vmem_budget=vmem_budget)
