"""Chain-fusion region formation (DESIGN.md §9).

Partitions a scheduled graph into maximal *chains* — linear runs of
consecutive packed ops (``packed_conv`` / ``packed_conv_pool`` /
``or_pool`` / ``maxpool_pm1``-on-packed) — that the executor's
``vpu_chain`` backend lowers into a **single Pallas call** whose
intermediates live in a VMEM scratch arena at planner-assigned offsets
(:mod:`repro.kernels.chain_conv`).  Only each chain's entry and exit touch
HBM; everything between runs at VMEM bandwidth with zero kernel-dispatch
boundaries, which is the paper's layers-integration discipline applied
*across* layers instead of within one.

Region-formation rules (§9.1):

* ops must be chainable (the set above; ``maxpool_pm1`` qualifies only
  when its input is already packed — then it is exactly an OR-pool, the
  same rewrite :func:`~repro.runtime.passes.absorb_pools` performs);
* the run must be a pure path: every non-tail member has exactly one
  consumer, the next member (fan-out forces a chain break — the branching
  value must be materialized);
* the chain's VMEM plan must fit the budget
  (:func:`~repro.runtime.memory.vmem_plan`) at a final tile of at least
  :data:`MIN_TILE_ROWS` rows: interior tile intermediates under lifetime
  first-fit reuse, plus the fixed residents (entry tile, weights and
  final tile, each double-buffered, and the conv row temporaries), all in
  the TPU's padded (8, 128) tiling
  (:func:`~repro.kernels.chain_conv.vmem_footprint`).  A run that exceeds
  the budget is split greedily — the longest fitting prefix becomes a
  region and the cut boundary spills to HBM;
* runs shorter than ``min_nodes`` (default 2) stay on the per-node path;
* a first binary conv that runs on the MXU straight from the uint8 image
  (:func:`~repro.runtime.graph.image_conv_expand`, the executor's
  ``mxu_image``) is no chain member: its region would start from the
  bit-plane words that lowering never builds.

Chains that fail any rule simply do not form; the executor evaluates
those nodes per-node with its normal backend fallback — there is no
error path, only a smaller fused region.
"""

from __future__ import annotations

import dataclasses
from typing import Mapping, Sequence

import jax.numpy as jnp

from repro.kernels.chain_conv import (VMEM_BUDGET, StageSpec,
                                      chain_word_counts, plan_tile)
from repro.runtime.graph import (PACKED_OPS, Graph, TensorType,
                                 image_conv_expand, infer_types)
from repro.runtime.memory import VmemPlan, vmem_plan

# A region must fit at a final tile of at least this many rows (or the
# whole map): thinner bands make the halo recompute dominate, so the run
# splits instead.
MIN_TILE_ROWS = 8

CHAIN_OPS = frozenset({"packed_conv", "packed_conv_pool", "or_pool",
                       "maxpool_pm1"})


def node_stages(node) -> tuple[StageSpec, ...]:
    """Lower one graph node to its kernel stage(s).  ``packed_conv_pool``
    decomposes into conv + pool stages — inside a chain the conv output
    goes to the VMEM arena either way, so the decomposition loses
    nothing and keeps the kernel walk uniform."""
    a = node.attrs
    if node.op in ("packed_conv", "packed_conv_pool"):
        stages = [StageSpec("conv", kernel=a["kernel"], stride=a["stride"],
                            pad_lo=a["pad"], pad_hi=a["pad"],
                            channels=a["channels"],
                            first=bool(a.get("first")))]
        if node.op == "packed_conv_pool":
            plo, phi = tuple(a.get("pool_pad", (0, 0)))
            stages.append(StageSpec("pool", kernel=a["pool_window"],
                                    stride=a["pool_stride"],
                                    pad_lo=plo, pad_hi=phi,
                                    channels=a["channels"]))
        return tuple(stages)
    if node.op in ("or_pool", "maxpool_pm1"):
        plo, phi = tuple(a.get("pad", (0, 0)))
        return (StageSpec("pool", kernel=a["window"], stride=a["stride"],
                          pad_lo=plo, pad_hi=phi,
                          channels=a.get("channels") or 0),)
    raise ValueError(f"op {node.op!r} is not chainable")


@dataclasses.dataclass
class Chain:
    """One fused region: schedule-ordered member nodes, their static
    kernel stages, the head's input shape, the VMEM plan at the default
    tile, and the (autotunable) tile config."""
    node_ids: tuple[int, ...]
    stages: tuple[StageSpec, ...]
    in_shape: tuple[int, ...]
    plan: VmemPlan
    tile: dict = dataclasses.field(default_factory=dict)

    @property
    def head(self) -> int:
        return self.node_ids[0]

    @property
    def tail(self) -> int:
        return self.node_ids[-1]

    def tiling(self, tile: Mapping[str, int] | None = None
               ) -> ChainTiling:
        """A tile config (default: this chain's own) resolved under this
        chain's budget — recomputed because tile shape changes the
        interior sizes the planner packs."""
        return plan_chain(self.stages, self.in_shape,
                          self.tile if tile is None else tile,
                          self.plan.budget)

    def hbm_bytes_avoided(self) -> int:
        """Whole-map HBM traffic the fusion removes: one store + one load
        per interior stage boundary (vs the per-node ``vpu_direct`` path,
        which round-trips every boundary — including the conv→pool
        boundary inside ``packed_conv_pool`` — through HBM)."""
        return stages_hbm_bytes_avoided(self.stages, self.in_shape)

    def signature_key(self) -> tuple:
        """Shape/op identity for autotune persistence (chain-shaped
        signatures; see :mod:`repro.runtime.autotune`)."""
        return (tuple(dataclasses.astuple(st) for st in self.stages),
                tuple(self.in_shape))


def stages_hbm_bytes_avoided(stages: Sequence[StageSpec],
                             in_shape: Sequence[int]) -> int:
    """One store + one load of every interior stage output at full-map
    size — the boundary traffic a fused chain never issues.  Shared by
    :meth:`Chain.hbm_bytes_avoided` and the kernel benchmark so the two
    reports can never diverge."""
    n, h, w = in_shape[0], in_shape[1], in_shape[2]
    cws = chain_word_counts(tuple(stages), in_shape[3])
    total = 0
    for k, st in enumerate(stages[:-1]):
        h, w = st.out_size(h), st.out_size(w)
        total += 2 * n * h * w * cws[k + 1] * 4
    return total


@dataclasses.dataclass(frozen=True)
class ChainTiling:
    """One chain tile config resolved: the kernel's tile arguments with
    ``block_h`` explicit, the VMEM plan, and the planned arena's row
    offsets (per interior stage output) and rows."""
    tile: dict
    vmem: VmemPlan
    arena_offsets: tuple[int, ...]
    arena_rows: int

    def kernel_args(self) -> dict:
        """Keyword arguments of :func:`~repro.kernels.ops.chain_forward`."""
        return dict(self.tile, arena_offsets=self.arena_offsets,
                    arena_rows=self.arena_rows)


def plan_chain(stages: Sequence[StageSpec], in_shape: Sequence[int],
               tile: Mapping[str, int] | None = None,
               budget: int = VMEM_BUDGET) -> ChainTiling:
    """Resolve a chain tile config (:func:`~repro.kernels.chain_conv.
    plan_tile`: ``block_h`` unset means the tallest band that fits
    ``budget``) and plan its VMEM: interior stage tiles (lifetime
    [k, k+1]) go through the planner's first-fit in whole arena rows;
    the fixed residents count toward the budget check."""
    tile = dict(tile or {})
    tp = plan_tile(stages, in_shape, tile.get("block_h"),
                   tile.get("block_w"), tile.get("block_n", 1), budget)
    vmem = vmem_plan(tp.sizes, budget=budget, fixed_bytes=tp.fixed)
    tile["block_h"] = tp.block_h
    return ChainTiling(tile, vmem,
                       tuple(o // tp.row_bytes for o in vmem.offsets),
                       vmem.arena_bytes // tp.row_bytes)


def build_chain(graph: Graph, node_ids: Sequence[int],
                input_shape: Sequence[int],
                types: Mapping[int, TensorType] | None = None,
                budget: int = VMEM_BUDGET) -> Chain:
    """Assemble a :class:`Chain` from explicit member ids (must be a valid
    path of chainable ops).  Public so tests can split chains at arbitrary
    boundaries."""
    types = types if types is not None else infer_types(
        graph, tuple(input_shape))
    node_ids = tuple(node_ids)
    stages: list[StageSpec] = []
    for nid in node_ids:
        stages.extend(node_stages(graph.nodes[nid]))
    in_shape = types[graph.nodes[node_ids[0]].inputs[0]].shape
    plan = plan_chain(stages, in_shape, budget=budget).vmem
    return Chain(node_ids=node_ids, stages=tuple(stages),
                 in_shape=in_shape, plan=plan)


def _fits(chain: Chain) -> bool:
    """Budget gate of region formation: the plan fits at a final tile of
    at least :data:`MIN_TILE_ROWS` rows (or the whole map)."""
    if not chain.plan.fits():
        return False
    rows = chain.in_shape[1]
    for st in chain.stages:
        rows = st.out_size(rows)
    return chain.tiling({}).tile["block_h"] >= min(rows, MIN_TILE_ROWS)


def _chainable(graph: Graph, nid: int) -> bool:
    node = graph.nodes[nid]
    if node.op not in CHAIN_OPS or image_conv_expand(graph, nid) is not None:
        return False
    if node.op == "maxpool_pm1":
        # Only exactly an OR-pool when the input is already packed words.
        prod = graph.nodes[node.inputs[0]]
        if prod.op not in PACKED_OPS:
            return False
    return True


def partition_chains(graph: Graph, input_shape: Sequence[int],
                     *, vmem_budget: int = VMEM_BUDGET,
                     min_nodes: int = 2,
                     types: Mapping[int, TensorType] | None = None
                     ) -> list[Chain]:
    """Partition the schedule into maximal budget-fitting chains."""
    types = types if types is not None else infer_types(
        graph, tuple(input_shape))
    cons = graph.consumers()
    schedule = graph.topo_order()
    used: set[int] = set()
    runs: list[list[int]] = []
    for nid in schedule:
        if nid in used or not _chainable(graph, nid):
            continue
        run = [nid]
        used.add(nid)
        cur = nid
        while True:
            users = cons[cur]
            if len(users) != 1:
                break
            nxt = users[0]
            if (nxt in used or not _chainable(graph, nxt)
                    or graph.nodes[nxt].inputs != (cur,)):
                break
            run.append(nxt)
            used.add(nxt)
            cur = nxt
        runs.append(run)

    chains: list[Chain] = []
    for run in runs:
        start = 0
        while start < len(run):
            # Longest prefix whose VMEM plan fits the budget.
            best = None
            for end in range(start + 1, len(run) + 1):
                cand = build_chain(graph, run[start:end], input_shape,
                                   types=types, budget=vmem_budget)
                if not _fits(cand):
                    break
                best = cand
            if best is None:          # even a single node busts the budget
                start += 1
                continue
            if len(best.node_ids) >= min_nodes:
                chains.append(best)
            start += len(best.node_ids)
    return chains


def chain_stage_arrays(chain: Chain, params_by_node: Mapping[str, Mapping]
                       ) -> tuple:
    """Flatten member-node params into the kernel's per-conv-stage tuple
    ``(w_packed, word_weights|None, threshold, sign_flip)``.  Looked up
    from the executor's *traced* param pytree so the arrays stay jit
    operands, never closure constants."""
    arrays: list = []
    for nid in chain.node_ids:
        p = params_by_node.get(str(nid), {})
        if "w_packed" not in p:
            continue                               # pool node: no params
        thr = p["thresh"]
        arrays += [p["w_packed"], p.get("word_weights"),
                   thr.threshold, thr.sign_flip]
    return tuple(arrays)


def eval_chain(chain: Chain, params_by_node: Mapping[str, Mapping],
               x: jnp.ndarray) -> jnp.ndarray:
    """Run one region through the megakernel (dispatch via
    :mod:`repro.kernels.ops` so interpret mode follows the platform)."""
    from repro.kernels import ops as kops

    return kops.chain_forward(
        x, chain.stages, chain_stage_arrays(chain, params_by_node),
        **chain.tiling().kernel_args())


def chain_executor(graph: Graph, input_shape: Sequence[int],
                   *, vmem_budget: int = VMEM_BUDGET,
                   tuner=None, donate_input: bool = False):
    """Build the region-fused executor: partition the schedule into
    budget-fitting chains, optionally sweep per-chain tile shapes with an
    :class:`~repro.runtime.autotune.Autotuner` (pass one on TPU; interpret
    -mode timings are validators, not contenders), and freeze everything
    into a :class:`~repro.runtime.executor.GraphExecutor` whose leftover
    per-node ops degrade along the normal fallback order."""
    from repro.runtime.executor import CHAIN_BACKEND, GraphExecutor

    chains = partition_chains(graph, input_shape, vmem_budget=vmem_budget)
    if tuner is not None:
        tuner.tune_chains(graph, chains)
    return GraphExecutor(graph, CHAIN_BACKEND, regions=chains,
                         donate_input=donate_input)


def chain_report(chains: Sequence[Chain]) -> list[dict]:
    """One row per region: members, stage count, arena plan, HBM savings."""
    rows = []
    for c in chains:
        rows.append(dict(
            nodes="+".join(map(str, c.node_ids)),
            n_stages=len(c.stages),
            in_shape="x".join(map(str, c.in_shape)),
            arena_bytes=c.plan.arena_bytes,
            vmem_bytes=c.plan.total_bytes(),
            hbm_bytes_avoided=c.hbm_bytes_avoided(),
            tile=dict(c.tile)))
    return rows
