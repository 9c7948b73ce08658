"""Operator IR for the PhoneBit graph runtime (DESIGN.md §4.1).

A model is a DAG of :class:`Node` objects with explicit edges, replacing the
flat ``LayerSpec`` walk of ``bnn_model.packed_forward``.  Explicit edges make
branching topologies (residual adds, multi-head detectors, concat trunks)
expressible, and give the optimization passes (:mod:`repro.runtime.passes`)
and the static memory planner (:mod:`repro.runtime.memory`) a substrate to
work on.

Two lowering entry points produce graphs:

* :func:`lower_packed` — from a ``converter.convert`` artifact (the serving
  path; works on loaded ``.npz`` artifacts where the float params are gone).
  Emits the *fused* ops (``packed_conv`` / ``packed_dense``) directly.
* :func:`lower_trained` — from trained latent-float params.  Emits the
  *unfused* pipeline (``conv_counts`` → ``bn_binarize``, ``maxpool_pm1``)
  so the fusion/absorption/layout passes can be exercised and tested as
  explicit rewrites; running the default pass pipeline converges to the
  same fused graph the artifact path produces.

Op vocabulary (``attrs`` are static python values; ``params`` are arrays):

===============  ============================================================
op               semantics (layouts in DESIGN.md §4.2)
===============  ============================================================
input            graph input placeholder; uint8 NHWC image
bitplane_expand  uint8 (N,H,W,C) → (N,H,W,8·Cw) int32 bit-plane words
packed_conv      fused conv+BN+binarize on packed words → packed words
packed_conv_pool packed_conv with an OR-pool epilogue fused in
                 (``passes.fuse_pool_epilogue``); the pre-pool conv output
                 is never materialized on the direct-kernel backend
packed_dense     fused dense+BN+binarize, flattens input → (N, Ow)
or_pool          max-pool in the packed domain = windowed bitwise OR
conv_counts      unfused conv: weighted xor-popcounts (N,OH,OW,O) int32
dense_counts     unfused dense counts (N, O) int32
bn_binarize      float-BN epilogue on counts → packed bits (oracle form)
threshold_pack   integer-threshold epilogue on counts → packed bits
maxpool_pm1      semantic max-pool: unpack ±1 → reduce-max → repack
unpack_pm1       packed words → float ±1 (c_per_pos valid channels)
float_dense      full-precision head: flatten, x@w+b
float_conv       full-precision conv (paper's conv9)
concat_packed    channel-concat of packed words (each input C ≡ 0 mod 32)
===============  ============================================================

Every node carries ``attrs["channels"]`` — the number of *valid* binary
channels per spatial position of its output — which downstream lowering and
the layout pass use to materialize unpack widths.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Sequence

import jax.numpy as jnp
import numpy as np

from repro.core import bitplanes, packing
from repro.core.binary_conv import conv_out_size, pack_conv_weights
from repro.core.bnn_model import (BConv, BDense, FloatConv, FloatDense,
                                  LayerSpec, Pool)
from repro.kernels.image_conv import exact

# Ops whose output stays in the packed-word domain.
PACKED_OPS = frozenset({
    "packed_conv", "packed_conv_pool", "packed_dense", "or_pool",
    "bn_binarize", "threshold_pack", "maxpool_pm1", "concat_packed",
})
# Ops the executor can dispatch to more than one backend.
DISPATCHABLE_OPS = frozenset({"packed_conv", "packed_conv_pool",
                              "packed_dense"})


@dataclasses.dataclass
class Node:
    """One operator instance.  ``inputs`` are producer node ids (explicit
    edges); ``attrs`` are static (hashed into the jit closure); ``params``
    are arrays traced as operands."""
    id: int
    op: str
    inputs: tuple[int, ...]
    attrs: dict[str, Any] = dataclasses.field(default_factory=dict)
    params: dict[str, Any] = dataclasses.field(default_factory=dict)

    def with_(self, **kw) -> "Node":
        return dataclasses.replace(self, **kw)


@dataclasses.dataclass
class Graph:
    nodes: dict[int, Node] = dataclasses.field(default_factory=dict)
    input_id: int = -1
    output_id: int = -1
    input_hw: tuple[int, int] | None = None

    # ---- construction ----------------------------------------------------
    def new_id(self) -> int:
        return max(self.nodes, default=-1) + 1

    def add(self, op: str, inputs: Sequence[int] = (), attrs=None,
            params=None) -> int:
        nid = self.new_id()
        self.nodes[nid] = Node(nid, op, tuple(inputs), dict(attrs or {}),
                               dict(params or {}))
        return nid

    # ---- structure -------------------------------------------------------
    def consumers(self) -> dict[int, list[int]]:
        out: dict[int, list[int]] = {nid: [] for nid in self.nodes}
        for node in self.nodes.values():
            for src in node.inputs:
                out[src].append(node.id)
        return out

    def topo_order(self) -> list[int]:
        """Deterministic topological order (Kahn, smallest-id first)."""
        indeg = {nid: len(set(n.inputs)) for nid, n in self.nodes.items()}
        cons = self.consumers()
        ready = sorted(nid for nid, d in indeg.items() if d == 0)
        order: list[int] = []
        while ready:
            nid = ready.pop(0)
            order.append(nid)
            for c in cons[nid]:
                uniq = set(self.nodes[c].inputs)
                indeg[c] -= 1 if nid in uniq else 0
                if indeg[c] == 0:
                    ready.append(c)
            ready.sort()
        if len(order) != len(self.nodes):
            raise ValueError("graph has a cycle")
        return order

    def copy(self) -> "Graph":
        return Graph(
            nodes={nid: Node(n.id, n.op, n.inputs, dict(n.attrs),
                             dict(n.params))
                   for nid, n in self.nodes.items()},
            input_id=self.input_id, output_id=self.output_id,
            input_hw=self.input_hw)

    def validate(self) -> None:
        for node in self.nodes.values():
            for src in node.inputs:
                if src not in self.nodes:
                    raise ValueError(f"node {node.id} ({node.op}) references "
                                     f"missing input {src}")
        if self.input_id not in self.nodes:
            raise ValueError("missing input node")
        if self.output_id not in self.nodes:
            raise ValueError("missing output node")
        self.topo_order()  # raises on cycles


def image_conv_expand(graph: Graph, nid: int) -> int | None:
    """The bit-plane expansion feeding node ``nid`` when ``nid`` is a first
    binary conv that can read the uint8 image behind it directly, as one
    exact integer conv on the MXU (:mod:`repro.kernels.image_conv`): the
    expansion's input is the image, and the sum's range, 255·K, stays
    exact under f32 accumulation.  None for every other node."""
    node = graph.nodes[nid]
    if (node.op not in ("packed_conv", "packed_conv_pool")
            or not node.attrs.get("first")):
        return None
    expand = graph.nodes[node.inputs[0]]
    if expand.op != "bitplane_expand":
        return None
    if not exact(node.attrs["kernel"] ** 2 * expand.attrs["c_in"]):
        return None
    return expand.id


# --------------------------------------------------------------------------
# Shape / dtype inference (memory planner + autotune substrate)
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TensorType:
    shape: tuple[int, ...]
    dtype: Any

    @property
    def nbytes(self) -> int:
        return int(np.prod(self.shape)) * np.dtype(self.dtype).itemsize


def _conv_hw(shape, k, stride, pad):
    return (conv_out_size(shape[1], k, stride, pad),
            conv_out_size(shape[2], k, stride, pad))


def infer_types(graph: Graph,
                input_shape: tuple[int, ...]) -> dict[int, TensorType]:
    """Output TensorType of every node given the graph-input shape."""
    types: dict[int, TensorType] = {}
    for nid in graph.topo_order():
        node = graph.nodes[nid]
        ins = [types[i] for i in node.inputs]
        a = node.attrs
        if node.op == "input":
            t = TensorType(tuple(input_shape), jnp.uint8)
        elif node.op == "bitplane_expand":
            n, h, w, c = ins[0].shape
            t = TensorType(
                (n, h, w, bitplanes.NUM_PLANES * packing.num_words(c)),
                jnp.int32)
        elif node.op in ("packed_conv", "packed_conv_pool", "conv_counts"):
            oh, ow = _conv_hw(ins[0].shape, a["kernel"], a["stride"],
                              a["pad"])
            if node.op == "packed_conv_pool":
                pp = sum(a.get("pool_pad", (0, 0)))
                oh = (oh + pp - a["pool_window"]) // a["pool_stride"] + 1
                ow = (ow + pp - a["pool_window"]) // a["pool_stride"] + 1
            last = (a["channels"] if node.op == "conv_counts"
                    else packing.num_words(a["channels"]))
            t = TensorType((ins[0].shape[0], oh, ow, last), jnp.int32)
        elif node.op in ("or_pool", "maxpool_pm1"):
            n, h, w, cw = ins[0].shape
            ph, pw = a.get("pad", (0, 0))
            oh = (h + ph + pw - a["window"]) // a["stride"] + 1
            ow = (w + ph + pw - a["window"]) // a["stride"] + 1
            t = TensorType((n, oh, ow, cw), jnp.int32)
        elif node.op == "packed_dense":
            t = TensorType(
                (ins[0].shape[0], packing.num_words(a["channels"])),
                jnp.int32)
        elif node.op == "dense_counts":
            t = TensorType((ins[0].shape[0], a["channels"]), jnp.int32)
        elif node.op in ("bn_binarize", "threshold_pack"):
            s = ins[0].shape
            t = TensorType(s[:-1] + (packing.num_words(s[-1]),), jnp.int32)
        elif node.op == "unpack_pm1":
            s = ins[0].shape
            t = TensorType(s[:-1] + (a["channels"],), jnp.float32)
        elif node.op == "float_dense":
            t = TensorType((ins[0].shape[0], a["channels"]), jnp.float32)
        elif node.op == "float_conv":
            oh, ow = _conv_hw(ins[0].shape, a["kernel"], a["stride"],
                              a["pad"])
            t = TensorType((ins[0].shape[0], oh, ow, a["channels"]),
                           jnp.float32)
        elif node.op == "concat_packed":
            base = ins[0].shape
            last = sum(i.shape[-1] for i in ins)
            t = TensorType(base[:-1] + (last,), jnp.int32)
        else:
            raise ValueError(f"no shape rule for op {node.op!r}")
        types[nid] = t
    return types


# --------------------------------------------------------------------------
# Lowering: LayerSpec + converter artifact -> fused graph
# --------------------------------------------------------------------------

def _input_channels(spec: Sequence[LayerSpec]) -> int | None:
    for layer in spec:
        if isinstance(layer, (BConv, FloatConv)):
            return layer.c_in
    return None

def lower_packed(spec: Sequence[LayerSpec], packed: Sequence[dict],
                 input_hw: tuple[int, int]) -> Graph:
    """Lower a flat spec + ``converter.convert`` artifact to a fused graph.

    This is the serving-path lowering (Fig 2's load step): it needs only the
    deployable artifact, so it also works for ``converter.load_artifact``
    output where the latent float params no longer exist.
    """
    g = Graph(input_hw=input_hw)
    cur = g.add("input", attrs=dict(channels=_input_channels(spec)))
    g.input_id = cur
    channels: int | None = None

    for layer, p in zip(spec, packed):
        if isinstance(layer, BConv):
            if layer.first:
                # Executors that run this conv on the MXU read the image
                # directly and skip the expansion (image_conv_expand).
                cur = g.add("bitplane_expand", [cur],
                            attrs=dict(c_in=layer.c_in, channels=layer.c_in))
            cur = g.add(
                "packed_conv", [cur],
                attrs=dict(kernel=layer.kernel, stride=layer.stride,
                           pad=layer.pad, channels=layer.c_out,
                           first=layer.first),
                params=dict(w_packed=p["w_packed"], thresh=p["thresh"],
                            **({"word_weights": p["word_weights"]}
                               if "word_weights" in p else {})))
            channels = layer.c_out
        elif isinstance(layer, Pool):
            cur = g.add("or_pool", [cur],
                        attrs=dict(window=layer.window, stride=layer.stride,
                                   pad=tuple(layer.pad), channels=channels))
        elif isinstance(layer, BDense):
            cur = g.add("packed_dense", [cur],
                        attrs=dict(channels=layer.d_out),
                        params=dict(w_packed=p["w_packed"],
                                    thresh=p["thresh"]))
            channels = layer.d_out
        elif isinstance(layer, FloatDense):
            cur = g.add("unpack_pm1", [cur],
                        attrs=dict(channels=int(p["c_per_pos"])))
            cur = g.add("float_dense", [cur],
                        attrs=dict(channels=layer.d_out),
                        params=dict(w=p["w"], b=p["b"]))
            channels = layer.d_out
        elif isinstance(layer, FloatConv):
            cur = g.add("unpack_pm1", [cur],
                        attrs=dict(channels=int(p["c_per_pos"])))
            cur = g.add("float_conv", [cur],
                        attrs=dict(kernel=layer.kernel, stride=layer.stride,
                                   pad=layer.pad, channels=layer.c_out),
                        params=dict(w=p["w"], b=p["b"]))
            channels = layer.c_out
        else:
            raise ValueError(f"cannot lower layer {layer!r}")
    g.output_id = cur
    g.validate()
    return g


# --------------------------------------------------------------------------
# Lowering: trained float params -> unfused graph (pass-pipeline input)
# --------------------------------------------------------------------------

def _first_layer_packed_weights(layer: BConv, w) -> tuple[jnp.ndarray,
                                                          jnp.ndarray]:
    cw = packing.num_words(layer.c_in)
    wp = packing.pack_signs(w, axis=2)                        # KH,KW,Cw,O
    wp = jnp.repeat(wp[:, :, None, :, :], bitplanes.NUM_PLANES, axis=2)
    wp = jnp.transpose(wp, (4, 0, 1, 2, 3)).reshape(layer.c_out, -1)
    ww = jnp.tile(bitplanes.plane_word_weights(cw),
                  layer.kernel * layer.kernel)
    return wp, ww


def lower_trained(spec: Sequence[LayerSpec], params: Sequence[dict],
                  input_hw: tuple[int, int]) -> Graph:
    """Lower trained latent-float params to the *unfused* graph.

    Weight bit-packing happens here (packing is layout, not fusion), but BN
    stays a float epilogue (``bn_binarize``), pools stay semantic max-pools
    (``maxpool_pm1``), and no layout adapters (``bitplane_expand`` /
    ``unpack_pm1``) are emitted — those are the job of the
    :mod:`repro.runtime.passes` pipeline, mirroring what
    ``converter.convert`` hard-codes today (Eqns 5-9, §V-B).
    """
    g = Graph(input_hw=input_hw)
    cur = g.add("input", attrs=dict(channels=_input_channels(spec)))
    g.input_id = cur
    h, w = input_hw
    channels: int | None = None
    flat = False

    for layer, p in zip(spec, params):
        if isinstance(layer, BConv):
            if layer.first:
                wp, ww = _first_layer_packed_weights(layer, p["w"])
                wb = jnp.where(p["w"] >= 0, 1.0, -1.0)
                w_sum = jnp.sum(wb, axis=(0, 1, 2))
                conv_params = dict(w_packed=wp, word_weights=ww)
                bn_extra = dict(w_sum=w_sum)
            else:
                conv_params = dict(w_packed=pack_conv_weights(p["w"]))
                bn_extra = {}
            cur = g.add("conv_counts", [cur],
                        attrs=dict(kernel=layer.kernel, stride=layer.stride,
                                   pad=layer.pad, channels=layer.c_out,
                                   first=layer.first, k_valid=layer.k_valid),
                        params=conv_params)
            cur = g.add("bn_binarize", [cur],
                        attrs=dict(k_valid=layer.k_valid, first=layer.first,
                                   channels=layer.c_out),
                        params=dict(gamma=p["gamma"], beta=p["beta"],
                                    mu=p["mu"], var=p["var"], **bn_extra))
            h = conv_out_size(h, layer.kernel, layer.stride, layer.pad)
            w = conv_out_size(w, layer.kernel, layer.stride, layer.pad)
            channels = layer.c_out
        elif isinstance(layer, Pool):
            cur = g.add("maxpool_pm1", [cur],
                        attrs=dict(window=layer.window, stride=layer.stride,
                                   pad=tuple(layer.pad), channels=channels))
            h = (h + sum(layer.pad) - layer.window) // layer.stride + 1
            w = (w + sum(layer.pad) - layer.window) // layer.stride + 1
        elif isinstance(layer, BDense):
            if not flat:
                assert h * w * channels == layer.d_in, (
                    f"BDense d_in={layer.d_in} != {h}x{w}x{channels}")
                w4 = p["w"].reshape(h, w, channels, layer.d_out)
                wp = pack_conv_weights(w4)
            else:
                wp = jnp.transpose(packing.pack_signs(p["w"], axis=0), (1, 0))
            cur = g.add("dense_counts", [cur],
                        attrs=dict(channels=layer.d_out,
                                   k_valid=layer.d_in),
                        params=dict(w_packed=wp))
            cur = g.add("bn_binarize", [cur],
                        attrs=dict(k_valid=layer.d_in, first=False,
                                   channels=layer.d_out),
                        params=dict(gamma=p["gamma"], beta=p["beta"],
                                    mu=p["mu"], var=p["var"]))
            channels = layer.d_out
            flat = True
        elif isinstance(layer, FloatDense):
            cur = g.add("float_dense", [cur],
                        attrs=dict(channels=layer.d_out),
                        params=dict(w=p["w"].astype(jnp.float32),
                                    b=p["b"].astype(jnp.float32)))
            channels = layer.d_out
            flat = True
        elif isinstance(layer, FloatConv):
            cur = g.add("float_conv", [cur],
                        attrs=dict(kernel=layer.kernel, stride=layer.stride,
                                   pad=layer.pad, channels=layer.c_out),
                        params=dict(w=p["w"].astype(jnp.float32),
                                    b=p["b"].astype(jnp.float32)))
            h = conv_out_size(h, layer.kernel, layer.stride, layer.pad)
            w = conv_out_size(w, layer.kernel, layer.stride, layer.pad)
            channels = layer.c_out
        else:
            raise ValueError(f"cannot lower layer {layer!r}")
    g.output_id = cur
    g.validate()
    return g
