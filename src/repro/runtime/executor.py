"""Topological graph executor with per-node backend dispatch (DESIGN.md §4.5).

Evaluates a :class:`~repro.runtime.graph.Graph` in its deterministic
schedule under one ``jax.jit`` closure: the graph structure, static attrs,
per-node backend choices and kernel tile shapes are compile-time constants;
only the parameter arrays and the input image are traced operands.
Per-node backends:

* ``"xla"``             pure-JAX xor+popcount (paper Eqn 1; always available),
* ``"xla_pm1"``         pure-JAX ±1-matmul reformulation (XLA maps it to the
                        platform matmul engine),
* ``"mxu_pm1"``         the ±1-matmul Pallas kernel on the MXU over im2col
                        patches (the weighted first layer keeps the pure-JAX
                        counts),
* ``"vpu_popcount"``    the fused im2col Pallas kernel (interpret off-TPU),
* ``"vpu_direct"``      the direct (im2col-free) Pallas kernel — conv ops
                        only (DESIGN.md §5),
* ``"vpu_direct_pool"`` the direct kernel with the OR-pool fused into its
                        epilogue — ``packed_conv_pool`` nodes only.

Above the per-node backends sits the region-level ``"vpu_chain"`` mode
(DESIGN.md §9): the executor accepts ``regions=`` — chains formed by
:mod:`repro.runtime.regions` — and evaluates each whole region in one
Pallas megakernel call with VMEM-resident intermediates; member nodes are
skipped in the schedule and nodes outside every region degrade per-node
along ``_FALLBACK``.

All backends are bit-exact w.r.t. each other, so backend choice is purely a
performance decision — which is what makes per-node autotuning
(:mod:`repro.runtime.autotune`) safe.  Backends that do not apply to an op
(e.g. ``vpu_direct`` on ``packed_dense``) degrade along ``_FALLBACK`` when
the executor is built from a single mode string, and are rejected when
explicitly assigned per node.

``trace_count`` increments only when JAX retraces the closure, which the
tests use to pin the no-recompile-at-serve-time contract.
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Mapping, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import (binary_conv, binary_ops, bitplanes,
                        layer_integration, packing)
from repro.core.bnn_model import _BN_EPS
from repro.obs import metrics as _obs_metrics
from repro.obs import trace as _trace
from repro.runtime.graph import DISPATCHABLE_OPS, Graph
from repro.serving import faults as _faults

BACKENDS = ("xla", "xla_pm1", "mxu_pm1", "vpu_popcount", "vpu_direct",
            "vpu_direct_pool")
# The region-level megakernel mode (DESIGN.md §9): not a per-node backend
# — chains are evaluated whole via ``regions`` — but a valid engine
# ``matmul_mode``; per-node leftovers degrade along _FALLBACK.
CHAIN_BACKEND = "vpu_chain"
ALL_MODES = BACKENDS + (CHAIN_BACKEND,)

_IMPL = {"xla": "xor", "xla_pm1": "pm1", "mxu_pm1": "pm1"}
# Graceful degradation when a single mode string hits an op it cannot run.
_FALLBACK = {"vpu_chain": "vpu_direct_pool",
             "vpu_direct_pool": "vpu_direct", "vpu_direct": "vpu_popcount"}


def valid_backends(op: str) -> tuple[str, ...]:
    """The backends an op can dispatch to (autotune candidate filter)."""
    if op == "packed_conv_pool":
        return BACKENDS
    if op == "packed_conv":
        return tuple(b for b in BACKENDS if b != "vpu_direct_pool")
    if op == "packed_dense":
        return ("xla", "xla_pm1", "mxu_pm1", "vpu_popcount")
    return ()


def resolve_backend(op: str, backend: str) -> str:
    """Degrade a requested mode along _FALLBACK until the op supports it."""
    requested = backend
    while backend not in valid_backends(op):
        if backend not in _FALLBACK:
            raise ValueError(
                f"backend {requested!r} unusable for op {op!r}; want one "
                f"of {valid_backends(op)} (or 'auto' at the engine)")
        backend = _FALLBACK[backend]
    return backend


def _pallas_interpret() -> bool:
    return jax.default_backend() != "tpu"


_DONATION_FILTER_INSTALLED = False


def _ignore_donation_warning() -> None:
    """Install (once) a lowest-priority filter for XLA's failed-donation
    warning — expected on every donated call off-TPU.  ``append=True``
    keeps caller-installed filters (including ``error``) winning."""
    global _DONATION_FILTER_INSTALLED
    if not _DONATION_FILTER_INSTALLED:
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable",
            append=True)
        _DONATION_FILTER_INSTALLED = True


def _pool_attrs(a: dict) -> tuple[int, int, tuple[int, int]] | None:
    if "pool_window" not in a:
        return None
    return (a["pool_window"], a["pool_stride"],
            tuple(a.get("pool_pad", (0, 0))))


def _eval_packed_conv(a: dict, p: dict, x, backend: str, tile: dict):
    from repro.kernels import ops as kops

    k, s, pad = a["kernel"], a["stride"], a["pad"]
    ww = p.get("word_weights")
    pool = _pool_attrs(a)
    block_kw = dict(tile) if backend.startswith("vpu") else {}
    if backend == "vpu_direct_pool":
        # Pool rides the direct kernel's epilogue: the pre-pool conv
        # output never reaches HBM.
        return kops.fused_binary_conv2d(
            x, p["w_packed"], p["thresh"], k, k, s, pad, word_weights=ww,
            mode="vpu_direct", pool=pool, **block_kw)
    out = kops.fused_binary_conv2d(
        x, p["w_packed"], p["thresh"], k, k, s, pad, word_weights=ww,
        mode=backend, **block_kw)
    if pool is not None:
        out = binary_conv.binary_or_maxpool(out, pool[0], pool[1],
                                            pad=pool[2])
    return out


def _eval_packed_dense(a: dict, p: dict, x, backend: str, tile: dict):
    from repro.kernels import ops as kops

    block_kw = dict(tile) if backend.startswith("vpu") else {}
    return kops.fused_binary_dense(x, p["w_packed"], p["thresh"],
                                   mode=backend, **block_kw)


def _eval_bn_binarize(a: dict, p: dict, cnt):
    sigma = jnp.sqrt(p["var"] + _BN_EPS)
    if a.get("first"):
        # wcnt -> Eqn-2 dot: s = 255*(K + w_sum)/2 - wcnt
        const = 255.0 * (jnp.float32(a["k_valid"]) +
                         p["w_sum"].astype(jnp.float32)) / 2.0
        dot = const - cnt.astype(jnp.float32)
    else:
        dot = jnp.float32(a["k_valid"]) - 2.0 * cnt.astype(jnp.float32)
    x3 = p["gamma"] * ((dot + p.get("bias", 0.0)) - p["mu"]) / sigma + p["beta"]
    return packing.pack_bits((x3 >= 0), axis=-1)


def _eval_maxpool_pm1(a: dict, x):
    xv = packing.unpack_to_pm1(x, a["channels"], dtype=jnp.float32)
    pad = tuple(a.get("pad", (0, 0)))
    if pad != (0, 0):
        xv = jnp.pad(xv, ((0, 0), pad, pad, (0, 0)), constant_values=-1.0)
    xv = lax.reduce_window(
        xv, -jnp.inf, lax.max,
        (1, a["window"], a["window"], 1),
        (1, a["stride"], a["stride"], 1), "VALID")
    return packing.pack_bits((xv >= 0), axis=-1)


def eval_node(node_op: str, attrs: dict, params: dict, inputs: list,
              backend: str = "xla", tile: dict | None = None):
    """Evaluate one node given its already-computed input values."""
    a, p = attrs, params
    tile = tile or {}
    if node_op == "bitplane_expand":
        planes = bitplanes.pack_bitplanes(inputs[0])
        n, h, w, np_, cw = planes.shape
        return planes.reshape(n, h, w, np_ * cw)
    if node_op in ("packed_conv", "packed_conv_pool"):
        return _eval_packed_conv(a, p, inputs[0], backend, tile)
    if node_op == "packed_dense":
        return _eval_packed_dense(a, p, inputs[0], backend, tile)
    if node_op == "or_pool":
        return binary_conv.binary_or_maxpool(
            inputs[0], a["window"], a["stride"],
            pad=tuple(a.get("pad", (0, 0))))
    if node_op == "conv_counts":
        return binary_conv.binary_conv2d_counts(
            inputs[0], p["w_packed"], a["kernel"], a["kernel"],
            a["stride"], a["pad"], word_weights=p.get("word_weights"))
    if node_op == "dense_counts":
        flat = inputs[0].reshape(inputs[0].shape[0], -1)
        return binary_ops.binary_dense_counts(flat, p["w_packed"])
    if node_op == "bn_binarize":
        return _eval_bn_binarize(a, p, inputs[0])
    if node_op == "threshold_pack":
        bits = layer_integration.apply_threshold(inputs[0], p["thresh"])
        return packing.pack_bits(bits, axis=-1)
    if node_op == "maxpool_pm1":
        return _eval_maxpool_pm1(a, inputs[0])
    if node_op == "unpack_pm1":
        return packing.unpack_to_pm1(inputs[0], a["channels"],
                                     dtype=jnp.float32)
    if node_op == "float_dense":
        flat = inputs[0].reshape(inputs[0].shape[0], -1)
        return flat @ p["w"] + p["b"]
    if node_op == "float_conv":
        return lax.conv_general_dilated(
            inputs[0], p["w"], (a["stride"], a["stride"]),
            [(a["pad"], a["pad"])] * 2,
            dimension_numbers=("NHWC", "HWIO", "NHWC")) + p["b"]
    if node_op == "concat_packed":
        return jnp.concatenate(inputs, axis=-1)
    raise ValueError(f"cannot evaluate op {node_op!r}")


class GraphExecutor:
    """Jit-compiled topological evaluator with frozen per-node backends.

    The backend map and per-node kernel tile shapes are part of the
    compile-time closure: changing them means building a new executor
    (``with_backends``), never silently retracing an existing one —
    serve-time calls hit the same compiled function.
    """

    def __init__(self, graph: Graph,
                 backends: str | Mapping[int, str] = "xla",
                 tile_configs: Mapping[int, Mapping[str, int]] | None = None,
                 donate_input: bool = False,
                 regions: Sequence[Any] | None = None,
                 mesh=None, data_axis: str = "data"):
        graph.validate()
        self.graph = graph
        self.donate_input = donate_input
        # Data-parallel execution: the whole graph runs per shard of the
        # batch under ``shard_map`` over ``mesh[data_axis]`` — Pallas
        # kernels cannot be partitioned automatically, so each device
        # runs the program on its own rows (params replicated).
        self.mesh, self.data_axis = mesh, data_axis
        # Fused regions (runtime.regions.Chain): each is evaluated whole by
        # the chain megakernel when the schedule reaches its head; member
        # nodes are skipped and the result binds to the tail's id.
        self.regions = tuple(regions or ())
        self._region_head = {c.head: c for c in self.regions}
        self._region_members = {nid for c in self.regions
                                for nid in c.node_ids}
        if len(self._region_members) != sum(len(c.node_ids)
                                            for c in self.regions):
            raise ValueError("regions overlap")
        if isinstance(backends, str):
            backends = {nid: resolve_backend(n.op, backends)
                        for nid, n in graph.nodes.items()
                        if n.op in DISPATCHABLE_OPS}
        self.backends: dict[int, str] = {
            nid: b for nid, b in backends.items()
            if graph.nodes[nid].op in DISPATCHABLE_OPS}
        for nid, b in self.backends.items():
            op = graph.nodes[nid].op
            if b not in BACKENDS:
                raise ValueError(f"unknown backend {b!r} for node {nid}; "
                                 f"want one of {BACKENDS}")
            if b not in valid_backends(op):
                raise ValueError(f"backend {b!r} does not apply to node "
                                 f"{nid} ({op})")
        self.tile_configs: dict[int, dict] = {
            nid: dict(cfg) for nid, cfg in (tile_configs or {}).items()
            if nid in self.backends and cfg}
        # Params are traced operands (a pytree keyed by node id);
        # IntegratedParams is a NamedTuple and flattens naturally.
        self.arrays = {str(nid): dict(n.params)
                       for nid, n in graph.nodes.items() if n.params}
        self._schedule = graph.topo_order()
        self.trace_count = 0
        self._node_jits: dict[int, Any] = {}  # traced_call's own cache
        run = self._run
        if mesh is not None:
            from jax.sharding import PartitionSpec as P

            run = jax.shard_map(self._run, mesh=mesh,
                                in_specs=(P(), P(data_axis)),
                                out_specs=P(data_axis), check_vma=False)
        if donate_input:
            # The serving path hands each batch's input buffer to the
            # device for reuse (arg 1 = x; arg 0, the params, is never
            # donated).  Off-TPU XLA declines uint8 donations with a
            # warning — donation is permission, not a requirement.
            _ignore_donation_warning()
            self._jitted = jax.jit(run, donate_argnums=(1,))
        else:
            self._jitted = jax.jit(run)

    # ---- execution -------------------------------------------------------
    def _run(self, arrays, x):
        self.trace_count += 1  # increments at trace time only
        # Runtime-wide retrace series (DESIGN.md §10.2).  This runs at
        # trace time only — a host-side side effect exactly like the
        # counter above — so the compiled hot path carries no obs work.
        _obs_metrics.get_registry().counter("runtime.retraces").inc()
        g = self.graph
        env: dict[int, Any] = {}
        for nid in self._schedule:
            node = g.nodes[nid]
            if node.op == "input":
                env[nid] = x
                continue
            if nid in self._region_members:
                if nid in self._region_head:
                    from repro.runtime import regions as _regions

                    chain = self._region_head[nid]
                    env[chain.tail] = _regions.eval_chain(
                        chain, arrays, env[node.inputs[0]])
                continue
            env[nid] = eval_node(
                node.op, node.attrs, arrays.get(str(nid), {}),
                [env[i] for i in node.inputs],
                backend=self.backends.get(nid, "xla"),
                tile=self.tile_configs.get(nid))
        return env[g.output_id]

    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        # Fault-injection site (DESIGN.md §11.1): host-side, before the
        # compiled closure — a plan can make this executable "fail" or
        # stall without touching what jit compiled.  Disabled: one read.
        if _faults._PLAN is not None:
            _faults.maybe_fault("executor.call", nodes=len(self._schedule))
        # The disabled-tracing fast path is one global read: no span
        # object, no frame beyond this test (DESIGN.md §10.4).
        if _trace._TRACER is None:
            return self._jitted(self.arrays, x)
        with _trace.span("executor.call", "runtime",
                         nodes=len(self._schedule),
                         regions=len(self.regions)):
            return self._jitted(self.arrays, x)

    # ---- traced (diagnostic) execution -----------------------------------
    def _node_fn(self, nid: int):
        """Per-node jit'd callables for :meth:`traced_call`, cached so
        repeated traced calls never re-trace.  Kept apart from the fused
        closure: building these does not touch ``trace_count``."""
        fn = self._node_jits.get(nid)
        if fn is None:
            node = self.graph.nodes[nid]
            if nid in self._region_head:
                from repro.runtime import regions as _regions

                chain = self._region_head[nid]
                fn = jax.jit(lambda arrays, x:
                             _regions.eval_chain(chain, arrays, x))
            else:
                op, attrs = node.op, dict(node.attrs)
                backend = self.backends.get(nid, "xla")
                tile = self.tile_configs.get(nid)
                fn = jax.jit(lambda params, *ins: eval_node(
                    op, attrs, params, list(ins), backend=backend,
                    tile=tile))
            self._node_jits[nid] = fn
        return fn

    def traced_call(self, x: jnp.ndarray) -> jnp.ndarray:
        """Per-node execution with one span per node / chain region.

        The diagnostic answer to "where did this forward's time go":
        walks the schedule host-side, blocking after every node so each
        span's duration is real wall time (the fused ``__call__`` cannot
        attribute time below the whole closure).  Bit-exact with
        ``__call__`` — same backends, same tiles, same region evaluation
        — and runs through its own per-node jit cache, so the fused
        closure is never retraced (``trace_count`` unchanged).  Blocking
        per node forfeits inter-node overlap: this is a profiling tool,
        not a serving path.
        """
        g = self.graph
        env: dict[int, Any] = {}
        with _trace.span("executor.traced_call", "runtime",
                         nodes=len(self._schedule)):
            for nid in self._schedule:
                node = g.nodes[nid]
                if node.op == "input":
                    env[nid] = x
                    continue
                if nid in self._region_members:
                    chain = self._region_head.get(nid)
                    if chain is None:
                        continue
                    label = "+".join(map(str, chain.node_ids))
                    with _trace.span(f"region.{label}", "executor",
                                     op="chain", stages=len(chain.stages)):
                        out = self._node_fn(nid)(self.arrays,
                                                 env[node.inputs[0]])
                        jax.block_until_ready(out)
                    env[chain.tail] = out
                    continue
                with _trace.span(f"node.{node.op}", "executor", node=nid,
                                 backend=self.backends.get(nid)) as sp:
                    out = self._node_fn(nid)(
                        self.arrays.get(str(nid), {}),
                        *[env[i] for i in node.inputs])
                    jax.block_until_ready(out)
                    sp.set(shape=list(getattr(out, "shape", ())))
                env[nid] = out
        return env[g.output_id]

    # ---- variants --------------------------------------------------------
    def with_backends(self, backends: str | Mapping[int, str],
                      tile_configs: Mapping[int, Mapping[str, int]]
                      | None = None) -> "GraphExecutor":
        return GraphExecutor(self.graph, backends, tile_configs,
                             donate_input=self.donate_input,
                             regions=self.regions, mesh=self.mesh,
                             data_axis=self.data_axis)

    def sharded(self, mesh, data_axis: str = "data") -> "GraphExecutor":
        """This executor run data-parallel over ``mesh[data_axis]``."""
        return GraphExecutor(self.graph, self.backends, self.tile_configs,
                             donate_input=self.donate_input,
                             regions=self.regions, mesh=mesh,
                             data_axis=data_axis)

    def backend_report(self) -> list[dict]:
        rows = []
        for nid in self._schedule:
            node = self.graph.nodes[nid]
            if nid in self._region_members:
                chain = self._region_head.get(nid)
                if chain is not None:
                    rows.append(dict(
                        node="+".join(map(str, chain.node_ids)), op="chain",
                        channels=self.graph.nodes[chain.tail]
                                     .attrs.get("channels"),
                        backend=CHAIN_BACKEND, tile=dict(chain.tile)))
                continue
            if node.op in DISPATCHABLE_OPS:
                rows.append(dict(node=nid, op=node.op,
                                 channels=node.attrs.get("channels"),
                                 backend=self.backends.get(nid, "xla"),
                                 tile=self.tile_configs.get(nid, {})))
        return rows
