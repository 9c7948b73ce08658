"""Per-kernel microbenchmarks → machine-readable ``BENCH_kernels.json``.

Three comparisons per fig5 (YOLOv2-Tiny) binary conv layer, all bit-exact
by construction, so the deltas are pure execution-engine effects:

* **reduction**: the unrolled outer-product xor+popcount walk
  (``reduction="vector"``) vs the per-word ``fori_loop`` form
  (``reduction="loop"``) inside ``xnor_popcount_matmul``, on the layer's
  im2col matmul shape.
* **conv path**: the direct (im2col-free) fused kernel vs the im2col
  fused kernel on the layer's conv shape.
* **chain**: the megakernel region starting at the layer (the layer's
  conv+pool plus the *next* graph node, DESIGN.md §9) as one Pallas call
  with VMEM-resident intermediates, vs the per-node ``vpu_direct`` path
  (direct kernel per conv, packed OR-pool between) — plus the HBM bytes
  the fusion avoids at each interior boundary.

Plus one **packing** row: the first-layer bit-plane split+pack kernel
(``bitplane_pack``) at conv1's input shape, so packing perf is tracked
alongside the conv kernels.

The JSON artifact records per-kernel latency, effective GB/s and the
backend winner so the perf trajectory is tracked across PRs (every run
overwrites ``BENCH_kernels.json`` at the repo root; CI's ``--smoke`` run
shrinks shapes but keeps the schema identical).

Off-TPU both Pallas kernels execute in ``interpret`` mode — absolute
numbers are then validator-grade only, but the loop/vector and
direct/im2col *ratios* still track the amount of work each form issues.
Shapes are scaled down (channel dims exact, spatial dims capped) to keep
interpret-mode timings tractable.
"""

from __future__ import annotations

import pathlib

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import write_bench
from benchmarks.timing import time_stable as _time_stable
from repro.core import binary_conv, layer_integration, packing
from repro.core.bnn_model import BConv, Pool
from repro.core.packing import num_words
from repro.kernels import ops as kops
from repro.kernels.chain_conv import StageSpec
from repro.kernels.direct_conv_bn_binarize import direct_conv_bn_binarize
from repro.runtime.regions import stages_hbm_bytes_avoided
from repro.kernels.xnor_popcount_matmul import xnor_popcount_matmul
from repro.models import paper_nets

BENCH_PATH = pathlib.Path("BENCH_kernels.json")

# Spatial grid entering each conv at full 416 res (fig5_layers), capped to
# keep interpret-mode popcount loops tractable on the host.
_SIZES = [416, 208, 104, 52, 26, 13, 13, 13]


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _gbps(nbytes: int, seconds: float) -> float:
    return nbytes / max(seconds, 1e-12) / 1e9


def _bench_layer(layer: BConv, h: int, m_red: int, rng,
                 iters: int) -> dict:
    """One fig5 conv layer: reduction + conv-path comparison."""
    kk, c_in, c_out = layer.kernel, layer.c_in, layer.c_out
    x = jnp.asarray(rng.choice([-1.0, 1.0],
                               (1, h, h, c_in)).astype(np.float32))
    w = jnp.asarray(rng.choice([-1.0, 1.0],
                               (kk, kk, c_in, c_out)).astype(np.float32))
    xp = packing.pack_signs(x, axis=-1)
    wp = binary_conv.pack_conv_weights(w)
    kv = kk * kk * c_in
    t = jnp.asarray(rng.integers(0, kv, c_out), jnp.int32)
    s = jnp.asarray(rng.integers(0, 2, c_out).astype(bool))
    p = layer_integration.IntegratedParams(t, s)
    interp = _interpret()

    # -- reduction comparison on the layer's im2col matmul shape ----------
    # m_red rows ≈ the layer's OH*OW at benchmark resolution — enough rows
    # to amortize per-block overhead so the loop/vector delta is resolvable
    # above host-timing noise.
    m, wdim = m_red, wp.shape[1]
    flat = jnp.asarray(
        rng.integers(-2**31, 2**31, (m, wdim), dtype=np.int64)
        .astype(np.int32))
    nbytes = 4 * (m * wdim + c_out * wdim + m * c_out)
    budget = 0.15 if iters == 1 else 0.3
    times = {}
    for red in ("vector", "loop"):
        f = lambda a, b: xnor_popcount_matmul(a, b, reduction=red,
                                              interpret=interp)
        times[red] = _time_stable(f, flat, wp, budget_s=budget)
    red_winner = min(times, key=times.get)

    # -- conv path: direct (im2col-free) vs im2col fused ------------------
    conv_times = {}
    conv_times["vpu_direct"] = _time_stable(
        lambda xx, ww: direct_conv_bn_binarize(
            xx, ww, t, s, kh=kk, kw=kk, stride=layer.stride, pad=layer.pad,
            interpret=interp),
        xp, wp, budget_s=budget, warmup=1)
    conv_times["vpu_popcount"] = _time_stable(
        lambda xx, ww: kops.fused_binary_conv2d(
            xx, ww, p, kk, kk, layer.stride, layer.pad,
            mode="vpu_popcount"),
        xp, wp, budget_s=budget, warmup=1)
    conv_winner = min(conv_times, key=conv_times.get)
    # Traffic of the conv that was actually timed (shape n=1, h x h):
    # direct reads the input once + filters and stores packed output;
    # im2col additionally materializes the (OH*OW, KH*KW*Cw) patch tensor.
    oh = binary_conv.conv_out_size(h, kk, layer.stride, layer.pad)
    m_conv = oh * oh
    out_words = m_conv * (-(-c_out // 32))
    direct_bytes = 4 * (xp.size + wp.size + out_words)
    im2col_bytes = 4 * (xp.size + 2 * m_conv * wdim + wp.size + out_words)

    return dict(
        grid=h, c_in=c_in, c_out=c_out, kernel=kk,
        matmul_shape=[int(m), int(c_out), int(wdim)],
        conv_positions=int(m_conv),
        reduction=dict(
            loop_ms=round(times["loop"] * 1e3, 3),
            vector_ms=round(times["vector"] * 1e3, 3),
            vector_speedup=round(times["loop"] / max(times["vector"],
                                                     1e-12), 2),
            vector_gbps=round(_gbps(nbytes, times["vector"]), 4),
            winner=red_winner),
        conv=dict(
            im2col_ms=round(conv_times["vpu_popcount"] * 1e3, 3),
            direct_ms=round(conv_times["vpu_direct"] * 1e3, 3),
            direct_speedup=round(
                conv_times["vpu_popcount"]
                / max(conv_times["vpu_direct"], 1e-12), 2),
            direct_gbps=round(
                _gbps(direct_bytes, conv_times["vpu_direct"]), 4),
            im2col_gbps=round(
                _gbps(im2col_bytes, conv_times["vpu_popcount"]), 4),
            patch_bytes_avoided=int(im2col_bytes - direct_bytes),
            winner=conv_winner),
    )


def _synth_conv(layer: BConv, rng):
    """Synthetic packed weights + integer epilogue for one conv layer."""
    kk = layer.kernel
    w = jnp.asarray(rng.choice([-1.0, 1.0],
                               (kk, kk, layer.c_in, layer.c_out))
                    .astype(np.float32))
    wp = binary_conv.pack_conv_weights(w)
    t = jnp.asarray(rng.integers(0, kk * kk * layer.c_in, layer.c_out),
                    jnp.int32)
    s = jnp.asarray(rng.integers(0, 2, layer.c_out).astype(bool))
    return wp, layer_integration.IntegratedParams(t, s)


def _bench_chain_row(span: list[tuple[BConv, Pool | None]], h: int, rng,
                     budget: float) -> dict:
    """Megakernel region vs the per-node ``vpu_direct`` path over the same
    span: each conv(+pool) graph node plus its successor, one Pallas call
    (intermediates in the VMEM arena) vs one direct kernel per conv with
    the packed OR-pool between (every boundary through HBM).  Both paths
    are asserted bit-exact before timing."""
    c_in = span[0][0].c_in
    x = jnp.asarray(packing.pack_signs(
        jnp.asarray(rng.choice([-1.0, 1.0], (1, h, h, c_in))
                    .astype(np.float32)), axis=-1))

    stages: list[StageSpec] = []
    arrays: list = []
    pernode_ops: list = []
    for conv, pool in span:
        wp, p = _synth_conv(conv, rng)
        stages.append(StageSpec("conv", conv.kernel, conv.stride,
                                conv.pad, conv.pad, channels=conv.c_out))
        arrays += [wp, None, p.threshold, p.sign_flip]
        pernode_ops.append(("conv", wp, p, conv))
        if pool is not None:
            stages.append(StageSpec("pool", pool.window, pool.stride,
                                    pool.pad[0], pool.pad[1],
                                    channels=conv.c_out))
            pernode_ops.append(("pool", pool))

    stages_t, arrays_t = tuple(stages), tuple(arrays)

    @jax.jit
    def pernode(xx):
        y = xx
        for op in pernode_ops:
            if op[0] == "conv":
                _, wp, p, conv = op
                y = kops.fused_binary_conv2d(
                    y, wp, p, conv.kernel, conv.kernel, conv.stride,
                    conv.pad, mode="vpu_direct")
            else:
                pool = op[1]
                y = binary_conv.binary_or_maxpool(y, pool.window,
                                                  pool.stride,
                                                  pad=tuple(pool.pad))
        return y

    chain = jax.jit(lambda xx: kops.chain_forward(xx, stages_t, arrays_t))
    np.testing.assert_array_equal(np.asarray(chain(x)),
                                  np.asarray(pernode(x)))

    t_chain = _time_stable(chain, x, budget_s=budget, warmup=1)
    t_node = _time_stable(pernode, x, budget_s=budget, warmup=1)

    # HBM traffic the fusion avoids, via the canonical accounting shared
    # with graph_plan's region report.
    avoided = stages_hbm_bytes_avoided(stages_t,
                                       (1, h, h, num_words(c_in)))

    return dict(
        span="+".join(f"{c.c_in}>{c.c_out}" + ("p" if p else "")
                      for c, p in span),
        n_stages=len(stages_t),
        chain_ms=round(t_chain * 1e3, 3),
        pernode_ms=round(t_node * 1e3, 3),
        chain_speedup=round(t_node / max(t_chain, 1e-12), 2),
        hbm_bytes_avoided=int(avoided),
        winner="vpu_chain" if t_chain < t_node else "vpu_direct")


def _bench_packing(h: int, rng, budget: float) -> dict:
    """First-layer bit-plane split + channel pack at conv1's input shape."""
    from repro.core.bitplanes import NUM_PLANES

    x = jnp.asarray(rng.integers(0, 256, (1, h, h, 3)), jnp.uint8)
    f = jax.jit(lambda xx: kops.bitplane_pack(xx))
    t = _time_stable(f, x, budget_s=budget, warmup=1)
    nbytes = int(x.size) + 4 * h * h * NUM_PLANES * num_words(3)
    return dict(grid=h, c_in=3,
                pack_ms=round(t * 1e3, 3),
                gbps=round(_gbps(nbytes, t), 4))


def run(smoke: bool = False, path: pathlib.Path | None = None) -> dict:
    spec, _ = paper_nets.get("yolov2-tiny")
    convs: list[tuple[BConv, Pool | None]] = []
    for j, l in enumerate(spec):
        if isinstance(l, BConv):
            nxt = spec[j + 1] if j + 1 < len(spec) else None
            convs.append((l, nxt if isinstance(nxt, Pool) else None))
    scale, cap, m_cap = (52, 4, 1024) if smoke else (16, 13, 4096)
    iters = 1 if smoke else 5
    budget = 0.15 if smoke else 0.3
    rng = np.random.default_rng(0)

    layers = {}
    for i, ((layer, pool), size) in enumerate(zip(convs, _SIZES), start=1):
        if layer.first:
            continue  # conv1 rides the bit-plane path; not a like-for-like
        h = min(max(size // scale, 4), cap)
        m_red = min(max((size // 4) ** 2, 169), m_cap)
        row = _bench_layer(layer, h, m_red, rng, iters)
        # Chain row: this graph node plus its successor (the last conv
        # spans nothing and runs as a single-stage region — no interior
        # boundary, so no HBM win is claimed for it).
        span = convs[i - 1:i + 1]
        row["chain"] = _bench_chain_row(span, h, rng, budget)
        layers[f"conv{i}"] = row

    pack_h = min(max(_SIZES[0] // scale, 4), cap * 2)
    packing_row = _bench_packing(pack_h, rng, budget)

    report = dict(
        schema="bench-kernels-v2",
        device_kind=jax.default_backend(),
        pallas_interpret=_interpret(),
        smoke=smoke,
        layers=layers,
        packing=packing_row,
        summary=dict(
            vector_wins=sum(r["reduction"]["winner"] == "vector"
                            for r in layers.values()),
            direct_wins=sum(r["conv"]["winner"] == "vpu_direct"
                            for r in layers.values()),
            chain_wins=sum(r["chain"]["winner"] == "vpu_chain"
                           for r in layers.values()),
            hbm_bytes_avoided=sum(r["chain"]["hbm_bytes_avoided"]
                                  for r in layers.values()),
            n_layers=len(layers)),
    )
    out = path or BENCH_PATH
    report = write_bench(out, report, sort_keys=True)
    s = report["summary"]
    print(f"# §Kernels — wrote {out} "
          f"({s['vector_wins']}/{len(layers)} layers: vectorized "
          f"reduction wins; {s['direct_wins']}/{len(layers)}: direct conv "
          f"wins; {s['chain_wins']}/{len(layers)}: chain wins, "
          f"{s['hbm_bytes_avoided']} HBM bytes avoided; packing "
          f"{packing_row['pack_ms']}ms @ grid {packing_row['grid']})")
    return report


def main(argv=None):
    import argparse

    ap = argparse.ArgumentParser(prog="benchmarks.kernels_bench")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized shapes; still writes "
                         "BENCH_kernels.json")
    args = ap.parse_args(argv)
    run(smoke=args.smoke)


if __name__ == "__main__":
    main()
