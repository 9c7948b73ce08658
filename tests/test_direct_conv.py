"""Direct (im2col-free) fused conv kernel + its runtime integration.

Bit-exactness of ``direct_conv_bn_binarize`` against the float BN oracle
and the canonical im2col path across the awkward-shape matrix
(non-block-multiple OH/OW/O, stride 2, pad 0/1, 1x1 pointwise, bit-plane
word weights), the pool-epilogue fusion pass, the ``vpu_direct``/
``vpu_direct_pool`` executor backends, the tile-shape autotuner and its
disk-persisted cache (DESIGN.md §5).
"""

import json

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax import lax

from repro.core import (binary_conv, bitplanes, bnn_model, converter,
                        layer_integration, packing)
from repro.core.bnn_model import BConv, BDense, FloatDense, Pool
from repro import runtime
from repro.kernels.direct_conv_bn_binarize import direct_conv_bn_binarize
from repro.kernels.xnor_popcount_matmul import xnor_popcount_matmul
from repro.runtime import (Autotuner, GraphExecutor, fuse_pool_epilogue,
                           lower_packed, plan_memory)
from repro.serving import PhoneBitEngine


def _float_oracle_packed(x_pm1, w, gamma, beta, mu, sigma, stride, pad):
    """binarize(BN(conv(x, w))) with the -1 padding convention, packed."""
    if pad:
        x_pm1 = jnp.pad(x_pm1, ((0, 0), (pad, pad), (pad, pad), (0, 0)),
                        constant_values=-1.0)
    dot = lax.conv_general_dilated(
        x_pm1, w, (stride, stride), [(0, 0)] * 2,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    bits = layer_integration.bn_reference(dot, gamma, beta, mu, sigma)
    return packing.pack_bits(bits, axis=-1)


class TestDirectConvKernel:
    """Kernel vs float oracle + im2col path over the shape matrix."""

    @pytest.mark.parametrize("h,c_in,c_out,kh,stride,pad,block_kw", [
        (8, 64, 32, 3, 1, 1, {}),                        # baseline
        (9, 64, 40, 3, 1, 1, {}),                        # O % 32 != 0, odd HW
        (10, 96, 33, 3, 2, 0, {}),                       # stride 2, pad 0
        (7, 64, 64, 1, 1, 0, {}),                        # 1x1 pointwise
        (11, 64, 32, 3, 1, 1, dict(block_h=3, block_w=4)),  # non-multiple
        (8, 33, 32, 3, 1, 1, dict(block_n=2)),           # ragged Cw + batch
        (8, 64, 32, 5, 2, 2, dict(block_h=2)),           # k5 s2 p2
    ])
    def test_vs_float_oracle_and_im2col(self, h, c_in, c_out, kh, stride,
                                        pad, block_kw):
        rng = np.random.default_rng(h * 31 + c_out)
        x = jnp.asarray(rng.choice([-1.0, 1.0], (2, h, h, c_in))
                        .astype(np.float32))
        w = jnp.asarray(rng.choice([-1.0, 1.0], (kh, kh, c_in, c_out))
                        .astype(np.float32))
        gamma = jnp.asarray(rng.uniform(-1.5, 1.5, c_out), jnp.float32)
        beta = jnp.asarray(rng.uniform(-1, 1, c_out), jnp.float32)
        mu = jnp.asarray(rng.uniform(-20, 20, c_out), jnp.float32)
        sigma = jnp.asarray(rng.uniform(0.5, 2, c_out), jnp.float32)
        p = layer_integration.fold_bn(kh * kh * c_in, gamma, beta, mu,
                                      sigma)

        xp = packing.pack_signs(x, axis=-1)
        wp = binary_conv.pack_conv_weights(w)
        got = direct_conv_bn_binarize(
            xp, wp, p.threshold, p.sign_flip, kh=kh, kw=kh, stride=stride,
            pad=pad, interpret=True, **block_kw)

        oracle = _float_oracle_packed(x, w, gamma, beta, mu, sigma,
                                      stride, pad)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(oracle))

        im2col = binary_conv.binary_conv2d_fused(xp, wp, p, kh, kh,
                                                 stride, pad)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(im2col))

    def test_bitplane_first_layer_word_weights(self):
        """Eqn-2 bit-plane word weights through the direct kernel."""
        rng = np.random.default_rng(3)
        c_in, c_out, kh, h = 3, 40, 3, 9
        x = jnp.asarray(rng.integers(0, 256, (2, h, h, c_in)), jnp.uint8)
        planes = bitplanes.pack_bitplanes(x)
        n, hh, ww_, np_, cw_ = planes.shape
        flat = planes.reshape(n, hh, ww_, np_ * cw_)
        w = jnp.asarray(rng.choice([-1.0, 1.0], (kh, kh, c_in, c_out))
                        .astype(np.float32))
        wp = packing.pack_signs(w, axis=2)
        wp = jnp.repeat(wp[:, :, None, :, :], bitplanes.NUM_PLANES, axis=2)
        wp = jnp.transpose(wp, (4, 0, 1, 2, 3)).reshape(c_out, -1)
        cw = packing.num_words(c_in)
        ww = jnp.tile(bitplanes.plane_word_weights(cw), kh * kh)
        t = jnp.asarray(rng.integers(0, 255 * kh * kh * c_in, c_out),
                        jnp.int32)
        s = jnp.asarray(rng.integers(0, 2, c_out).astype(bool))
        p = layer_integration.IntegratedParams(t, s)
        ref = binary_conv.binary_conv2d_fused(flat, wp, p, kh, kh, 1, 1,
                                              word_weights=ww)
        got = direct_conv_bn_binarize(flat, wp, t, s, kh=kh, kw=kh,
                                      stride=1, pad=1, word_weights=ww,
                                      interpret=True, block_h=4)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))

    @pytest.mark.parametrize("pool,block_kw", [
        ((2, 2, (0, 0)), {}),                         # plain pool
        ((2, 2, (0, 0)), dict(block_h=2, block_w=3)), # tiled pool epilogue
        ((2, 1, (0, 1)), {}),                         # yolo same-pool pad
        ((3, 2, (0, 0)), dict(block_h=2)),            # window 3
    ])
    def test_pool_epilogue(self, pool, block_kw):
        rng = np.random.default_rng(11)
        h, c_in, c_out, kh = 13, 64, 48, 3
        window, pstride, ppad = pool
        x = jnp.asarray(rng.choice([-1.0, 1.0], (2, h, h, c_in))
                        .astype(np.float32))
        w = jnp.asarray(rng.choice([-1.0, 1.0], (kh, kh, c_in, c_out))
                        .astype(np.float32))
        xp = packing.pack_signs(x, axis=-1)
        wp = binary_conv.pack_conv_weights(w)
        kv = kh * kh * c_in
        t = jnp.asarray(rng.integers(0, kv, c_out), jnp.int32)
        s = jnp.asarray(rng.integers(0, 2, c_out).astype(bool))
        p = layer_integration.IntegratedParams(t, s)
        conv = binary_conv.binary_conv2d_fused(xp, wp, p, kh, kh, 1, 1)
        ref = binary_conv.binary_or_maxpool(conv, window, pstride, pad=ppad)
        got = direct_conv_bn_binarize(
            xp, wp, t, s, kh=kh, kw=kh, stride=1, pad=1,
            pool_window=window, pool_stride=pstride, pool_pad=ppad,
            interpret=True, **block_kw)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


class TestVectorizedReduction:
    """The whole-tile reduction == the legacy per-word loop form."""

    @pytest.mark.parametrize("m,n,k", [(10, 7, 65), (33, 40, 96)])
    def test_loop_vs_vector(self, m, n, k):
        rng = np.random.default_rng(m + n + k)
        a = packing.pack_signs(
            jnp.asarray(rng.choice([-1.0, 1.0], (m, k)).astype(np.float32)))
        b = packing.pack_signs(
            jnp.asarray(rng.choice([-1.0, 1.0], (n, k)).astype(np.float32)))
        v = xnor_popcount_matmul(a, b, block_m=16, block_n=16, block_k=2,
                                 reduction="vector", interpret=True)
        l = xnor_popcount_matmul(a, b, block_m=16, block_n=16, block_k=2,
                                 reduction="loop", interpret=True)
        np.testing.assert_array_equal(np.asarray(v), np.asarray(l))


# --------------------------------------------------------------------------
# Runtime integration
# --------------------------------------------------------------------------

def _pool_net():
    return [
        BConv(c_in=3, c_out=16, kernel=3, stride=1, pad=1, first=True),
        Pool(window=2, stride=2),
        BConv(c_in=16, c_out=40, kernel=3, stride=1, pad=1),
        Pool(window=2, stride=1, pad=(0, 1)),
        BDense(d_in=8 * 8 * 40, d_out=64),
        FloatDense(d_in=64, d_out=10),
    ]


def _randomize_bn(params, seed=42):
    rng = np.random.default_rng(seed)
    for p in params:
        if "mu" in p:
            o = p["mu"].shape[0]
            p["mu"] = jnp.asarray(rng.uniform(-20, 20, o), jnp.float32)
            p["var"] = jnp.asarray(rng.uniform(0.5, 4, o), jnp.float32)
            p["gamma"] = jnp.asarray(rng.uniform(-1.5, 1.5, o), jnp.float32)
            p["beta"] = jnp.asarray(rng.uniform(-1, 1, o), jnp.float32)
    return params


@pytest.fixture(scope="module")
def pooly():
    spec = _pool_net()
    params = _randomize_bn(bnn_model.init_params(jax.random.key(4), spec))
    packed = converter.convert(params, spec, (16, 16))
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.integers(0, 256, (2, 16, 16, 3)), jnp.uint8)
    return spec, params, packed, x


class TestPoolFusionPass:

    def test_rewrites_and_stays_exact(self, pooly):
        spec, _, packed, x = pooly
        g = lower_packed(spec, packed, (16, 16))
        gf = fuse_pool_epilogue(g)
        ops = [gf.nodes[i].op for i in gf.topo_order()]
        assert "or_pool" not in ops
        assert ops.count("packed_conv_pool") == 2
        np.testing.assert_array_equal(
            np.asarray(GraphExecutor(g, "xla")(x)),
            np.asarray(GraphExecutor(gf, "xla")(x)))

    def test_fanout_blocks_fusion(self, pooly):
        spec, _, packed, x = pooly
        g = lower_packed(spec, packed, (16, 16))
        # Give the first conv a second consumer: its unpooled map must
        # stay materialized, so the pool cannot be absorbed.
        conv_id = next(nid for nid in g.topo_order()
                       if g.nodes[nid].op == "packed_conv")
        g.output_id = g.add("concat_packed", [conv_id, conv_id],
                            attrs=dict(channels=32))
        gf = fuse_pool_epilogue(g)
        assert any(n.op == "or_pool" for n in gf.nodes.values())

    def test_peak_bytes_drop_on_conv_heavy_graph(self, pooly):
        """The direct path materializes no im2col buffer and (pool-fused)
        no unpooled conv map: the planned arena must shrink."""
        spec, _, packed, x = pooly
        g = lower_packed(spec, packed, (16, 16))
        gf = fuse_pool_epilogue(g)
        p0 = plan_memory(g, (1, 16, 16, 3)).peak_bytes()
        p1 = plan_memory(gf, (1, 16, 16, 3)).peak_bytes()
        assert p1 < p0

    def test_infer_types_matches_execution(self, pooly):
        spec, _, packed, x = pooly
        gf = fuse_pool_epilogue(lower_packed(spec, packed, (16, 16)))
        types = runtime.infer_types(gf, x.shape)
        ex = GraphExecutor(gf, "xla")
        out = ex(x)
        assert tuple(out.shape) == types[gf.output_id].shape


class TestDirectBackends:

    def test_all_backends_bit_exact(self, pooly):
        spec, _, packed, x = pooly
        gf = fuse_pool_epilogue(lower_packed(spec, packed, (16, 16)))
        ref = bnn_model.packed_forward(packed, spec, x[:1])
        for backend in ("xla", "xla_pm1", "vpu_popcount", "vpu_direct",
                        "vpu_direct_pool"):
            got = GraphExecutor(gf, backend)(x[:1])
            np.testing.assert_array_equal(np.asarray(got), np.asarray(ref),
                                          err_msg=backend)

    def test_backend_validity(self, pooly):
        spec, _, packed, _ = pooly
        gf = fuse_pool_epilogue(lower_packed(spec, packed, (16, 16)))
        dense = next(nid for nid in gf.topo_order()
                     if gf.nodes[nid].op == "packed_dense")
        with pytest.raises(ValueError):
            GraphExecutor(gf, {dense: "vpu_direct"})
        assert runtime.valid_backends("packed_conv_pool") == runtime.BACKENDS
        assert "vpu_direct_pool" not in runtime.valid_backends("packed_conv")

    def test_tile_configs_are_static_and_exact(self, pooly):
        spec, _, packed, x = pooly
        gf = fuse_pool_epilogue(lower_packed(spec, packed, (16, 16)))
        convs = [nid for nid in gf.topo_order()
                 if gf.nodes[nid].op == "packed_conv_pool"]
        ex = GraphExecutor(gf, {nid: "vpu_direct_pool" for nid in convs},
                           {convs[0]: dict(block_h=2, block_n=2)})
        ref = bnn_model.packed_forward(packed, spec, x)
        np.testing.assert_array_equal(np.asarray(ex(x)), np.asarray(ref))
        ex(x)
        assert ex.trace_count == 1
        assert any(r["tile"] for r in ex.backend_report())

    def test_engine_direct_modes_cross_check(self, pooly):
        spec, params, _, x = pooly
        for mode in ("vpu_direct", "vpu_direct_pool"):
            engine = PhoneBitEngine.from_trained(params, spec, (16, 16),
                                                 matmul_mode=mode)
            engine.cross_check(x[:1])  # graph path == flat oracle
            report = engine.backend_choices
            assert any(r["op"] == "packed_conv_pool" for r in report)
            assert all(r["backend"] == "vpu_popcount"
                       for r in report if r["op"] == "packed_dense")

    def test_engine_matches_float_oracle(self, pooly):
        spec, params, _, x = pooly
        engine = PhoneBitEngine.from_trained(params, spec, (16, 16),
                                             matmul_mode="vpu_direct_pool")
        got = engine(x[:1])
        ref = bnn_model.float_forward(params, spec, x[:1])
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=0, atol=1e-3)


class TestAutotuneTilesAndCache:

    def test_tune_with_tiles_direct_candidates(self, pooly):
        spec, _, packed, x = pooly
        gf = fuse_pool_epilogue(lower_packed(spec, packed, (16, 16)))
        tuner = Autotuner(candidates=("xla", "vpu_direct",
                                      "vpu_direct_pool"),
                          warmup=0, iters=1)
        choices, tiles = tuner.tune_with_tiles(gf, (1, 16, 16, 3))
        assert choices
        for nid, b in choices.items():
            assert b in runtime.valid_backends(gf.nodes[nid].op)
        # direct candidates were swept with tile configs
        entry = next(iter(tuner.cache.values()))
        assert any("[" in lbl for lbl in entry["timings_ms"])
        ex = GraphExecutor(gf, choices, tiles)
        ref = bnn_model.packed_forward(packed, spec, x)
        np.testing.assert_array_equal(np.asarray(ex(x)), np.asarray(ref))

    def test_disk_cache_roundtrip(self, pooly, tmp_path, monkeypatch):
        from repro.obs import metrics as obs_metrics

        spec, _, packed, x = pooly
        path = tmp_path / "autotune.json"
        monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(path))
        gf = fuse_pool_epilogue(lower_packed(spec, packed, (16, 16)))
        t1 = Autotuner(candidates=("xla", "xla_pm1"), warmup=0, iters=1)
        with obs_metrics.use_registry() as reg1:
            choices, _ = t1.tune_with_tiles(gf, (1, 16, 16, 3))
        # every fresh sweep leaves a structured miss event per signature
        evs = reg1.events("autotune")
        assert [e["outcome"] for e in evs] == ["miss"] * len(t1.cache)
        assert all(e["sweep_size"] >= 2 for e in evs)  # 2+ candidates
        assert {e["signature"] for e in evs} == set(t1.cache)
        assert reg1.counter("autotune.miss").value == len(t1.cache)
        assert path.exists()
        persisted = json.loads(path.read_text())
        # each measurement persists twice: under its exact signature and
        # under the batch-agnostic one (cross-bucket warm start)
        assert len(persisted) == 2 * len(t1.cache)
        assert all(k in persisted for k in t1.cache)
        assert sum(k.startswith("batchless::") for k in persisted) == \
            len(t1.cache)
        assert all(e["winner"] in ("xla", "xla_pm1")
                   for e in persisted.values())
        # A fresh tuner (fresh in-memory cache) warm-starts from disk:
        # same winners, no new timing entries written.
        mtime = path.stat().st_mtime_ns
        t2 = Autotuner(candidates=("xla", "xla_pm1"), warmup=0, iters=1)
        with obs_metrics.use_registry() as reg2:
            choices2, _ = t2.tune_with_tiles(gf, (1, 16, 16, 3))
        assert choices2 == choices
        assert path.stat().st_mtime_ns == mtime
        # ...and the warm start is visible as disk_hit events, no misses
        assert reg2.counter("autotune.disk_hit").value == len(t2.cache)
        assert reg2.counter("autotune.miss").value == 0

    def test_stale_disk_entry_is_disk_miss(self, pooly, tmp_path,
                                           monkeypatch):
        # A disk table written under a different jax/jaxlib must not
        # warm-start: the timings belong to another compiler.  Every
        # stale entry is a structured disk_miss + a fresh sweep, and the
        # re-sweep rewrites the table under the current env stamp.
        from repro.obs import metrics as obs_metrics
        from repro.runtime.autotune import entry_env_ok

        spec, _, packed, _ = pooly
        path = tmp_path / "autotune.json"
        monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(path))
        gf = fuse_pool_epilogue(lower_packed(spec, packed, (16, 16)))
        t1 = Autotuner(candidates=("xla", "xla_pm1"), warmup=0, iters=1)
        t1.tune_with_tiles(gf, (1, 16, 16, 3))
        table = json.loads(path.read_text())
        for e in table.values():
            e["env"] = {"jax": "0.0.1", "jaxlib": "0.0.1"}
        path.write_text(json.dumps(table))

        t2 = Autotuner(candidates=("xla", "xla_pm1"), warmup=0, iters=1)
        with obs_metrics.use_registry() as reg:
            t2.tune_with_tiles(gf, (1, 16, 16, 3))
        assert reg.counter("autotune.disk_hit").value == 0
        assert reg.counter("autotune.disk_miss").value == len(t2.cache)
        assert reg.counter("autotune.miss").value == len(t2.cache)
        assert {e["outcome"] for e in reg.events("autotune")} == \
            {"disk_miss", "miss"}
        # the fresh sweep re-stamped every persisted entry
        rewritten = json.loads(path.read_text())
        assert all(entry_env_ok(e) for e in rewritten.values())

    def test_escape_hatch_disables_persistence(self, pooly, tmp_path,
                                               monkeypatch):
        spec, _, packed, _ = pooly
        monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", "0")
        assert runtime.cache_path() is None
        gf = fuse_pool_epilogue(lower_packed(spec, packed, (16, 16)))
        tuner = Autotuner(candidates=("xla",), warmup=0, iters=1)
        tuner.tune(gf, (1, 16, 16, 3))  # must not write anywhere
        assert not list(tmp_path.iterdir())
