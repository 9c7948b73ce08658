"""Graph runtime: operator IR, optimization passes, memory planning, and a
multi-backend autotuned executor (DESIGN.md §4).

The engine-level half of the PhoneBit reproduction: where ``repro.core``
provides the kernels and the offline parameter transform, this package
provides the *framework* that composes them — the difference §V-C of the
paper (and daBNN/CNNdroid before it) draws between a fast kernel and a
fast engine.

    graph      operator IR (explicit-edge DAG) + lowering from LayerSpec
               sequences, converter artifacts, and trained float params
    passes     layout assignment, conv+BN+binarize integration (Eqns 5-9),
               epilogue fusion, OR-pool absorption — as testable rewrites
    memory     static lifetime analysis + arena planning (peak_bytes)
    executor   jit-compiled topological evaluator, per-node backends
    autotune   times backend candidates per node/chain, caches winners
    regions    chain-fusion region formation: runs of packed ops fused
               into single megakernel calls with VMEM-resident
               intermediates at planner offsets (DESIGN.md §9)
    placement  multi-device placement: pipeline cut candidates at HBM
               touch points, cost-balanced stage planning, and the
               staged per-device executor (DESIGN.md §13)
"""

from repro.runtime.autotune import (Autotuner, cache_path,
                                    default_candidates)
from repro.runtime.executor import (ALL_MODES, BACKENDS, CHAIN_BACKEND,
                                    IMAGE_BACKEND, GraphExecutor,
                                    valid_backends)
from repro.runtime.graph import (DISPATCHABLE_OPS, Graph, Node, TensorType,
                                 infer_types, lower_packed, lower_trained)
from repro.runtime.memory import MemoryPlan, VmemPlan, plan_memory, vmem_plan
from repro.runtime.passes import (absorb_pools, assign_layouts,
                                  default_pipeline, fuse_epilogues,
                                  fuse_pool_epilogue, integrate_bn)
from repro.runtime.placement import (StagedExecutor, StagePlan,
                                     cut_candidates, plan_pipeline,
                                     stage_subgraph, staged_executor)
from repro.runtime.regions import (Chain, build_chain, chain_executor,
                                   chain_report, partition_chains)

__all__ = [
    "ALL_MODES", "Autotuner", "BACKENDS", "CHAIN_BACKEND", "Chain",
    "DISPATCHABLE_OPS", "Graph", "GraphExecutor", "IMAGE_BACKEND",
    "MemoryPlan", "Node", "StagePlan", "StagedExecutor", "TensorType",
    "VmemPlan",
    "absorb_pools", "assign_layouts", "build_chain", "cache_path",
    "chain_executor", "chain_report", "cut_candidates",
    "default_candidates", "default_pipeline", "fuse_epilogues",
    "fuse_pool_epilogue", "infer_types", "integrate_bn", "lower_packed",
    "lower_trained", "partition_chains", "plan_memory", "plan_pipeline",
    "stage_subgraph", "staged_executor", "valid_backends", "vmem_plan",
]
