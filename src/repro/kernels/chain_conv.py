"""Pallas TPU megakernel: a multi-layer binary-conv chain in one call.

PhoneBit's layer-integration thesis (§V-C) taken one level up: every
layer boundary of a per-node path round-trips a packed activation through
HBM and pays a kernel dispatch.  On a 32x-compressed tensor that boundary
traffic and dispatch overhead rival the compute (daBNN, 1908.05858,
measures the same shift on ARM once the binary ops are cheap).  This
kernel executes a whole *region* — a static chain of conv / pool stages —
in a single ``pallas_call``; a one- or two-stage chain is the direct
(im2col-free) conv(+pool) kernel (``direct_conv_bn_binarize``):

* the chain **entry** streams one packed NHWC input tile into VMEM through
  element-offset block dims (``pl.Element``): adjacent tiles overlap by
  the chain's halo, so each step re-reads only that halo;
* every **interior** stage output is stored to a VMEM scratch **arena**
  at the row offset the memory planner assigned
  (:func:`repro.runtime.memory.vmem_plan` — lifetime-aware first-fit, so
  stage i and stage i+2 ping-pong into shared rows), and the next stage
  reads its input back from there — HBM is touched only at the chain's
  entry and exit;
* stages run one output row at a time (a ``fori_loop``).  A conv row walks
  KH x KW as strided row reads of its input (``pl.ds`` with the conv
  stride on the sublane axis), feeds each tap's (width, Cw) word tile to
  the outer-product xor+popcount walk
  (:func:`~repro.kernels.xnor_popcount_matmul.tile_counts`), then applies
  the integer threshold + 32-channel bit-pack; a pool row is a windowed
  bitwise OR of strided row reads.  Every value the kernel computes is
  2-D (positions on sublanes, words or channels on lanes), the form the
  TPU's Mosaic compiler lowers.

Tiling couples the stages through **halo growth**: to emit a
``(block_h, block_w)`` tile of the *final* stage, stage k must produce a
tile grown backwards through every later kernel window and stride, so the
entry tile (and the per-stage recompute overlap between adjacent grid
steps) grows with chain depth — which is why per-chain tile shapes are an
autotuning search space (DESIGN.md §9.3).  The default tile is the
tallest final-row block whose VMEM footprint (:func:`vmem_footprint`)
fits :data:`VMEM_BUDGET`: the whole map for small inputs, row bands at
416x416.

Correctness at tile and image borders: every position is computed in the
final stage's coordinate frame and mapped backwards affinely
(``origin = hi * step - offset``), so interior tiles read real neighbor
data while border tiles run past a stage's valid extent.  Out-of-range
positions of each interior stage are masked to zero words before the
arena store — the zero word is 32 channels of -1, which is simultaneously
this codebase's conv-padding convention and the OR-pool identity
(DESIGN.md §3.2), so the masked store *is* the next stage's padding.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.packing import WORD_BITS, num_words
from repro.kernels.fused_conv_bn_binarize import threshold_pack
from repro.kernels.xnor_popcount_matmul import (VMEM_LIMIT_BYTES,
                                                compiler_params, tile_counts)

# What one chain's planned footprint may occupy: half the scoped limit the
# kernel is compiled with, leaving the rest to Mosaic's own temporaries.
VMEM_BUDGET = VMEM_LIMIT_BYTES // 2


@dataclasses.dataclass(frozen=True)
class StageSpec:
    """One static chain stage.  ``kind`` is ``"conv"`` (fused binary conv +
    integer threshold + pack; ``kernel``/``stride``/``pad_*`` are the conv
    geometry, ``channels`` the valid output channels) or ``"pool"``
    (windowed OR over packed words; ``kernel`` is the pool window).
    Hashable so a chain spec can be a jit-static argument."""
    kind: str
    kernel: int
    stride: int
    pad_lo: int = 0
    pad_hi: int = 0
    channels: int = 0
    first: bool = False

    def out_size(self, size: int) -> int:
        return (size + self.pad_lo + self.pad_hi - self.kernel) \
            // self.stride + 1


@dataclasses.dataclass(frozen=True)
class _Geometry:
    """Host-side tile geometry for one (chain, tile-shape) pairing."""
    out_tile: tuple[tuple[int, int], ...]   # per-stage output tile (th, tw)
    out_step: tuple[tuple[int, int], ...]   # tile-origin step per grid inc
    out_off: tuple[tuple[int, int], ...]    # tile-origin static offset
    valid_hw: tuple[tuple[int, int], ...]   # per-stage valid output extent
    entry_tile: tuple[int, int]
    entry_step: tuple[int, int]
    entry_off: tuple[int, int]              # == top/left pre-pad of entry
    final_hw: tuple[int, int]


def chain_geometry(stages: tuple[StageSpec, ...], h: int, w: int,
                   block_h: int | None, block_w: int | None) -> _Geometry:
    """Backward halo propagation: from the final (block_h, block_w) output
    tile, grow each stage's required tile through its window and stride.
    Tile origins are affine in the grid index: ``origin = gi*step - off``.
    """
    hs, ws = [h], [w]
    for st in stages:
        hs.append(st.out_size(hs[-1]))
        ws.append(st.out_size(ws[-1]))
    fh, fw = hs[-1], ws[-1]
    th, tw = min(block_h or fh, fh), min(block_w or fw, fw)

    out_tile, out_step, out_off, valid = [], [], [], []
    mh, oh, mw, ow = th, 0, tw, 0
    for k in reversed(range(len(stages))):
        st = stages[k]
        out_tile.append((th, tw))
        out_step.append((mh, mw))
        out_off.append((oh, ow))
        valid.append((hs[k + 1], ws[k + 1]))
        th = (th - 1) * st.stride + st.kernel
        tw = (tw - 1) * st.stride + st.kernel
        mh, oh = mh * st.stride, oh * st.stride + st.pad_lo
        mw, ow = mw * st.stride, ow * st.stride + st.pad_lo
    return _Geometry(
        out_tile=tuple(reversed(out_tile)),
        out_step=tuple(reversed(out_step)),
        out_off=tuple(reversed(out_off)),
        valid_hw=tuple(reversed(valid)),
        entry_tile=(th, tw), entry_step=(mh, mw), entry_off=(oh, ow),
        final_hw=(fh, fw))


def chain_word_counts(stages: tuple[StageSpec, ...], cw_in: int
                      ) -> list[int]:
    """Packed word count entering each stage (index 0 = chain input) and
    leaving the last (index len(stages))."""
    cws = [cw_in]
    for st in stages:
        cws.append(num_words(st.channels) if st.kind == "conv" else cws[-1])
    return cws


def _round(n: int, m: int) -> int:
    return -(-n // m) * m


def tile_bytes(rows: int, width: int, words: int) -> int:
    """VMEM bytes of ``rows`` int32 (width, words) slabs in the TPU's
    native (8, 128) tiling: sublanes round to 8, lanes to 128."""
    return 4 * rows * _round(width, 8) * _round(words, 128)


def arena_shape(geo: _Geometry, cws: tuple[int, ...]) -> tuple[int, int]:
    """(width, words) of one arena row: the widest interior tile and the
    most interior words.  Each interior stage owns ``block_n * rows`` of
    these rows and uses its top-left (tile width, words) corner."""
    inner = range(len(geo.out_tile) - 1)
    return (max([geo.out_tile[k][1] for k in inner], default=1),
            max([cws[k + 1] for k in inner], default=1))


def vmem_footprint(stages: tuple[StageSpec, ...], in_shape, bn: int,
                   geo: _Geometry) -> tuple[list[int], int]:
    """(interior arena byte sizes, fixed bytes) for one tile config.

    The fixed part counts what Pallas and the kernel hold besides the
    arena, in the TPU's padded tiling: the entry, output and every
    whole-weight operand block twice (Pallas double-buffers them), plus
    the widest conv row's count/bit temporaries."""
    cw0 = in_shape[3]
    cws = chain_word_counts(tuple(stages), cw0)
    aw, ac = arena_shape(geo, tuple(cws))
    row_bytes = tile_bytes(1, aw, ac)
    sizes = [bn * th * row_bytes for th, _ in geo.out_tile[:-1]]
    ih, iw = geo.entry_tile
    fh, fw = geo.out_tile[-1]
    fixed = 2 * (tile_bytes(bn * ih, iw, cw0)
                 + tile_bytes(bn * fh, fw, cws[-1]))
    temps = 0
    for k, st in enumerate(stages):
        if st.kind != "conv":
            continue
        o_pad = num_words(st.channels) * WORD_BITS
        taps = st.kernel * st.kernel * cws[k]
        fixed += 2 * (tile_bytes(1, taps, o_pad) + tile_bytes(1, 1, taps)
                      + 2 * tile_bytes(1, 1, o_pad))
        temps = max(temps, 4 * tile_bytes(1, geo.out_tile[k][1], o_pad))
    return sizes, fixed + temps


@dataclasses.dataclass(frozen=True)
class TilePlan:
    """One tile request resolved: the geometry, the batch block, and the
    VMEM footprint (:func:`vmem_footprint`) it occupies."""
    geo: _Geometry
    bn: int
    sizes: tuple[int, ...]      # interior stage output tiles, bytes
    fixed: int                  # operand blocks + conv row temporaries
    row_bytes: int              # one (width, words) arena row

    @property
    def block_h(self) -> int:
        return self.geo.out_tile[-1][0]

    def total(self) -> int:
        """Footprint with no arena reuse."""
        return sum(self.sizes) + self.fixed

    def fits(self, budget: int = VMEM_BUDGET) -> bool:
        return self.total() <= budget


def plan_tile(stages: tuple[StageSpec, ...], in_shape,
              block_h: int | None = None, block_w: int | None = None,
              block_n: int = 1, budget: int = VMEM_BUDGET) -> TilePlan:
    """The one place a tile request is resolved.  With ``block_h`` unset:
    the tallest final-row block whose footprint fits ``budget`` (the
    whole map when it fits).  When not even one row fits, the one-row
    plan, whose :meth:`TilePlan.fits` is False."""
    stages, in_shape = tuple(stages), tuple(in_shape)
    n, h, w = in_shape[0], in_shape[1], in_shape[2]
    bn = max(1, min(block_n, n))
    cws = tuple(chain_word_counts(stages, in_shape[3]))

    def plan(bh):
        geo = chain_geometry(stages, h, w, bh, block_w)
        sizes, fixed = vmem_footprint(stages, in_shape, bn, geo)
        return TilePlan(geo, bn, tuple(sizes), fixed,
                        tile_bytes(1, *arena_shape(geo, cws)))

    if block_h is not None:
        return plan(block_h)
    fh = chain_geometry(stages, h, w, None, block_w).final_hw[0]
    for bh in sorted({-(-fh // k) for k in range(1, fh + 1)},
                     reverse=True):
        tp = plan(bh)
        if tp.fits(budget):
            return tp
    return tp


def _conv_row(read, st: StageSpec, w_ref, ww_ref, t_ref, s_ref, r,
              cw: int):
    """One output row of a conv stage: (width, nw) packed words."""
    acc = None
    for di in range(st.kernel):
        for dj in range(st.kernel):
            base = (di * st.kernel + dj) * cw
            c = tile_counts(read(r * st.stride + di, dj),
                            w_ref[base:base + cw, :],
                            ww_ref[:, base:base + cw])
            acc = c if acc is None else acc + c
    return threshold_pack(acc, t_ref[...], s_ref[...])


def _pool_row(read, st: StageSpec, r):
    """One output row of a pool stage: windowed bitwise OR over packed
    words (max-pool in the packed domain); zero words are the OR
    identity, so masked pad positions never distort the max."""
    out = None
    for i in range(st.kernel):
        for j in range(st.kernel):
            v = read(r * st.stride + i, j)
            out = v if out is None else (out | v)
    return out


def _mask_row(y, row, col0, valid):
    """Zero positions of one output row outside the stage's valid extent.
    The tile origin is ``gi*step - off`` (dynamic in the grid index), so
    border tiles cover pad-region coordinates — zeroing them reproduces
    the packed-domain padding convention for the next stage."""
    cols = col0 + jax.lax.broadcasted_iota(jnp.int32, y.shape, 0)
    ok = ((row >= 0) & (row < valid[0]) & (cols >= 0) & (cols < valid[1]))
    return jnp.where(ok, y, 0)


def _kernel(*refs, stages: tuple[StageSpec, ...], geo: _Geometry,
            cws: tuple[int, ...], arena_offsets: tuple[int, ...], bn: int):
    """One grid step: walk the whole chain for one final-output tile.
    ``refs`` = entry tile, 4 refs per conv stage (w^T, ww, t, s), output
    tile, then the (rows, width, words) int32 VMEM arena scratch."""
    hi, wi = pl.program_id(1), pl.program_id(2)
    x_ref, refs = refs[0], refs[1:]
    arena_ref, o_ref, param_refs = refs[-1], refs[-2], refs[:-2]
    last = len(stages) - 1
    pi = 0
    for k, st in enumerate(stages):
        th, tw = geo.out_tile[k]
        conv_refs = ()
        if st.kind == "conv":
            conv_refs = param_refs[pi:pi + 4]
            pi += 4
        for b in range(bn):
            if k == 0:
                def read(row, col, _b=b, _st=st, _tw=tw):
                    return x_ref[_b, row, pl.ds(col, _tw, _st.stride), :]
            else:
                def read(row, col, _st=st, _tw=tw, _cw=cws[k],
                         _base=arena_offsets[k - 1]
                         + b * geo.out_tile[k - 1][0]):
                    return arena_ref[_base + row,
                                     pl.ds(col, _tw, _st.stride),
                                     pl.ds(0, _cw)]

            def body(r, carry, _k=k, _b=b, _st=st, _read=read,
                     _conv=conv_refs, _th=th, _tw=tw):
                if _st.kind == "conv":
                    y = _conv_row(_read, _st, *_conv, r, cws[_k])
                else:
                    y = _pool_row(_read, _st, r)
                if _k == last:
                    o_ref[_b, r] = y
                else:
                    # Interior boundary: mask pad-region positions to zero
                    # words and store at the planner's arena rows — the
                    # next stage reads them straight back out of VMEM.
                    sh, sw = geo.out_step[_k]
                    oh, ow = geo.out_off[_k]
                    y = _mask_row(y, hi * sh - oh + r, wi * sw - ow,
                                  geo.valid_hw[_k])
                    arena_ref[arena_offsets[_k] + _b * _th + r,
                              pl.ds(0, _tw), pl.ds(0, cws[_k + 1])] = y
                return carry

            jax.lax.fori_loop(0, th, body, 0)


@functools.partial(
    jax.jit,
    static_argnames=("stages", "block_h", "block_w", "block_n",
                     "arena_offsets", "arena_rows", "interpret", "name"))
def chain_conv(x_packed: jnp.ndarray, stages: tuple[StageSpec, ...],
               stage_arrays: tuple[jnp.ndarray, ...],
               *, block_h: int | None = None, block_w: int | None = None,
               block_n: int = 1,
               arena_offsets: tuple[int, ...] | None = None,
               arena_rows: int | None = None,
               interpret: bool = False,
               name: str = "chain_region") -> jnp.ndarray:
    """Run a static conv/pool chain in one Pallas call.

    x_packed: (N, H, W, Cw) int32 packed words (bit-plane words for a
        first-layer entry).
    stages: static chain spec; ``stage_arrays`` carries, per conv stage in
        order, ``(w_packed (O, K*K*Cw), word_weights (K*K*Cw,) | None,
        threshold (O,), sign_flip (O,))`` — pool stages carry nothing.
    block_h / block_w / block_n: final-output tile; ``block_h`` unset
        picks the tallest that fits :data:`VMEM_BUDGET` (``ValueError``
        when not even one row does).  On the TPU a
        ``block_w`` that splits the width must be a multiple of 8.
    arena_offsets / arena_rows: arena row offset per interior stage output
        and total arena rows, normally from the memory planner's
        :func:`~repro.runtime.memory.vmem_plan`; defaulted to a dense
        no-reuse layout when omitted (kernel-level tests).
    name: the kernel's name on the device (its HLO instruction):
        ``chain_region`` for a fused region, ``direct_conv`` /
        ``direct_conv_pool`` for one node on the direct backends.
    Returns (N, FH, FW, ceil(O_last/32)) int32 (pool chains keep Cw).
    """
    n, h, w_in, cw0 = x_packed.shape
    tp = plan_tile(stages, x_packed.shape, block_h, block_w, block_n)
    if block_h is None and not tp.fits():
        raise ValueError(
            f"chain needs {tp.total()} B of VMEM even at one output row, "
            f"over VMEM_BUDGET ({VMEM_BUDGET} B)")
    geo, bn = tp.geo, tp.bn
    fh, fw = geo.final_hw
    bh, bw = geo.out_tile[-1]
    cws = tuple(chain_word_counts(stages, cw0))

    if arena_offsets is None:
        offs, total = [], 0
        for th, _ in geo.out_tile[:-1]:
            offs.append(total)
            total += bn * th
        arena_offsets, arena_rows = tuple(offs), total

    # Pad + widen per-stage operands: output channels to word multiples
    # with threshold=-1 / sign=0 so pad bits are 0 (pack_bits semantics);
    # filters transposed to (K*K*Cw, O) for the outer-product walk.
    ops: list[jnp.ndarray] = []
    ai = 0
    for st in stages:
        if st.kind != "conv":
            continue
        w_p, ww, t, s = stage_arrays[ai:ai + 4]
        ai += 4
        o, pw = w_p.shape
        o_pad = num_words(st.channels) * WORD_BITS
        if ww is None:
            ww = jnp.ones((pw,), jnp.int32)
        ops += [jnp.pad(w_p, ((0, o_pad - o), (0, 0))).T,
                ww.astype(jnp.int32).reshape(1, pw),
                jnp.pad(t.astype(jnp.int32), (0, o_pad - o),
                        constant_values=-1).reshape(1, o_pad),
                jnp.pad(s.astype(jnp.int32), (0, o_pad - o)
                        ).reshape(1, o_pad)]

    gn, gh, gw = pl.cdiv(n, bn), pl.cdiv(fh, bh), pl.cdiv(fw, bw)
    ih, iw = geo.entry_tile
    rstep, cstep = geo.entry_step
    top, left = geo.entry_off
    # Entry pre-pad: the chain's cumulative left/top pad plus bottom/right
    # slack so every grown halo read stays in bounds (0-words == -1
    # channels == the packed-domain conv pad).
    need_h = (gh - 1) * rstep + ih
    need_w = (gw - 1) * cstep + iw
    x_packed = jnp.pad(x_packed, (
        (0, gn * bn - n),
        (top, max(0, need_h - h - top)),
        (left, max(0, need_w - w_in - left)),
        (0, 0)))

    nw_out = cws[-1]
    # Mosaic must prove the sublane (W) offset a multiple of 8: a single
    # column tile uses a literal 0, a split one a ``block_w`` multiple of 8.
    cstep = cstep if gw > 1 else 0
    in_specs = [pl.BlockSpec(
        (pl.Element(bn), pl.Element(ih), pl.Element(iw), pl.Element(cw0)),
        lambda ni, hi, wi: (ni * bn, hi * rstep, wi * cstep, 0))]
    for arr in ops:
        in_specs.append(pl.BlockSpec(arr.shape, lambda ni, hi, wi: (0, 0)))

    out = pl.pallas_call(
        functools.partial(_kernel, stages=stages, geo=geo, cws=cws,
                          arena_offsets=arena_offsets, bn=bn),
        grid=(gn, gh, gw),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bn, bh, bw, nw_out),
                               lambda ni, hi, wi: (ni, hi, wi, 0)),
        out_shape=jax.ShapeDtypeStruct((gn * bn, gh * bh, gw * bw, nw_out),
                                       jnp.int32),
        scratch_shapes=[pltpu.VMEM(
            (max(arena_rows, 1),) + arena_shape(geo, cws), jnp.int32)],
        interpret=interpret,
        compiler_params=compiler_params(("parallel",) * 3),
        name=name,
    )(x_packed, *ops)
    return out[:n, :fh, :fw, :]
