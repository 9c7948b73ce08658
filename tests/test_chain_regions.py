"""Chain-fusion megakernel regions (DESIGN.md §9): kernel bit-exactness,
region formation + VMEM budgeting, executor integration, chain autotune,
and the memory-plan report regression."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import binary_conv, bnn_model, converter, layer_integration, \
    packing
from repro.core.bnn_model import BConv, BDense, FloatDense, Pool
from repro import runtime
from repro.kernels import ops as kops
from repro.kernels.chain_conv import (StageSpec, chain_conv, chain_geometry,
                                      plan_tile)
from repro.runtime import (Autotuner, GraphExecutor, build_chain,
                           chain_executor, lower_packed, partition_chains,
                           plan_memory, vmem_plan)
from repro.runtime import regions as regions_mod
from repro.serving import PhoneBitEngine


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


def _fused_graph(spec, hw, seed=0, bn_seed=11):
    params = _randomize_bn(bnn_model.init_params(jax.random.key(seed), spec),
                           seed=bn_seed)
    packed = converter.convert(params, spec, hw)
    return runtime.fuse_pool_epilogue(lower_packed(spec, packed, hw)), packed


def _conv_pair(rng, c_in, c_out, k):
    w = jnp.asarray(rng.choice([-1.0, 1.0],
                               (k, k, c_in, c_out)).astype(np.float32))
    wp = binary_conv.pack_conv_weights(w)
    t = jnp.asarray(rng.integers(0, k * k * c_in, c_out), jnp.int32)
    s = jnp.asarray(rng.integers(0, 2, c_out).astype(bool))
    return wp, layer_integration.IntegratedParams(t, s)


CHAIN_NET = [
    BConv(3, 16, kernel=3, stride=1, pad=1, first=True),
    Pool(2, 2),
    BConv(16, 40, kernel=3, stride=2, pad=1),   # stride-2, non-mult-32 O
    Pool(2, 1, pad=(0, 1)),                     # darknet 'same' pool
    BConv(40, 32, kernel=1, stride=1, pad=0),   # 1x1, pad 0
]


# --------------------------------------------------------------------------
# Kernel level: chain_conv vs per-node composition
# --------------------------------------------------------------------------

class TestChainKernel:

    @pytest.fixture(scope="class")
    def three_stage(self):
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.choice([-1.0, 1.0],
                                   (2, 10, 10, 32)).astype(np.float32))
        xp = packing.pack_signs(x, axis=-1)
        wp1, p1 = _conv_pair(rng, 32, 48, 3)
        wp2, p2 = _conv_pair(rng, 48, 64, 3)
        y1 = kops.fused_binary_conv2d(xp, wp1, p1, 3, 3, 1, 1, mode="xla")
        y1p = binary_conv.binary_or_maxpool(y1, 2, 2)
        ref = kops.fused_binary_conv2d(y1p, wp2, p2, 3, 3, 1, 1, mode="xla")
        stages = (StageSpec("conv", 3, 1, 1, 1, channels=48),
                  StageSpec("pool", 2, 2, channels=48),
                  StageSpec("conv", 3, 1, 1, 1, channels=64))
        arrays = (wp1, None, p1.threshold, p1.sign_flip,
                  wp2, None, p2.threshold, p2.sign_flip)
        return xp, stages, arrays, np.asarray(ref)

    def test_single_tile_matches_per_node(self, three_stage):
        xp, stages, arrays, ref = three_stage
        got = kops.chain_forward(xp, stages, arrays)
        np.testing.assert_array_equal(np.asarray(got), ref)

    @pytest.mark.parametrize("tile", [
        dict(block_h=2), dict(block_h=3, block_w=2),
        dict(block_h=2, block_n=2), dict(block_h=5, block_w=5)])
    def test_tiled_halo_matches_per_node(self, three_stage, tile):
        """Spatial tiling grows every stage's tile backwards through the
        chain (halo coupling); border tiles cover pad-region coordinates
        that must read as zero words."""
        xp, stages, arrays, ref = three_stage
        got = kops.chain_forward(xp, stages, arrays, **tile)
        np.testing.assert_array_equal(np.asarray(got), ref)

    def test_padded_pool_tail(self, three_stage):
        xp, stages, arrays, ref = three_stage
        stages = stages + (StageSpec("pool", 2, 1, 0, 1, channels=64),)
        want = binary_conv.binary_or_maxpool(jnp.asarray(ref), 2, 1,
                                             pad=(0, 1))
        got = kops.chain_forward(xp, stages, arrays, block_h=3)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_planner_offsets_reuse_arena(self, three_stage):
        """Passing vmem_plan offsets (ping-pong reuse) is bit-identical to
        the dense default layout — the plan is load-bearing, not lossy."""
        xp, stages, arrays, ref = three_stage
        stages = stages + (StageSpec("conv", 1, 1, 0, 0, channels=32),)
        rng = np.random.default_rng(5)
        wp3, p3 = _conv_pair(rng, 64, 32, 1)
        arrays = arrays + (wp3, None, p3.threshold, p3.sign_flip)
        tiling = regions_mod.plan_chain(stages, xp.shape)
        plan = tiling.vmem
        # three interior buffers with lifetimes [k, k+1]: first and third
        # must share space, so the planned arena beats the no-reuse sum
        assert len(plan.offsets) == 3
        assert plan.arena_bytes < plan.naive_bytes()
        assert plan.offsets[0] == plan.offsets[2]
        assert tiling.arena_offsets[0] == tiling.arena_offsets[2]
        got = kops.chain_forward(xp, stages, arrays, **tiling.kernel_args())
        want = kops.chain_forward(xp, stages, arrays)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_default_tile_over_budget_raises(self):
        """When not even one output row fits the VMEM budget, the kernel
        names the budget instead of handing Mosaic a tile it cannot
        place (traced only: no array of this size is made)."""
        stages = (StageSpec("conv", 3, 1, 1, 1, channels=32),)
        shape = (1, 8, 1 << 16, 128)
        assert not plan_tile(stages, shape).fits()
        sds = jax.ShapeDtypeStruct
        with pytest.raises(ValueError, match="VMEM_BUDGET"):
            jax.eval_shape(
                lambda x, w, t, s: chain_conv(x, stages, (w, None, t, s)),
                sds(shape, jnp.int32), sds((32, 9 * 128), jnp.int32),
                sds((32,), jnp.int32), sds((32,), jnp.int32))

    def test_geometry_halo_growth(self):
        """Entry tile = final tile grown through every window and stride."""
        stages = (StageSpec("conv", 3, 1, 1, 1, channels=32),
                  StageSpec("pool", 2, 2, channels=32),
                  StageSpec("conv", 3, 1, 1, 1, channels=32))
        geo = chain_geometry(stages, 16, 16, 4, 4)
        assert geo.out_tile[-1] == (4, 4)
        # conv3 tile 4 needs 6 pool rows; pool needs (6-1)*2+2 = 12 conv1
        # rows; conv1 needs (12-1)*1+3 = 14 entry rows
        assert geo.out_tile[1] == (6, 6)
        assert geo.out_tile[0] == (12, 12)
        assert geo.entry_tile == (14, 14)
        # origin affine: steps multiply through strides, offsets add pads
        assert geo.entry_step == (8, 8)
        assert geo.entry_off == (3, 3)


# --------------------------------------------------------------------------
# Region formation + vmem planning
# --------------------------------------------------------------------------

class TestRegions:

    @pytest.fixture(scope="class")
    def net(self):
        g, packed = _fused_graph(CHAIN_NET, (16, 16), seed=1)
        rng = np.random.default_rng(2)
        x = jnp.asarray(rng.integers(0, 256, (2, 16, 16, 3)), jnp.uint8)
        ref = np.asarray(GraphExecutor(g, "xla")(x))
        return g, x, ref

    def test_partition_forms_maximal_chain(self, net):
        g, x, _ = net
        chains = partition_chains(g, x.shape)
        assert len(chains) == 1
        chain = chains[0]
        ops = [g.nodes[nid].op for nid in chain.node_ids]
        assert all(o in regions_mod.CHAIN_OPS for o in ops)
        # The first conv runs on the MXU from the image, in no chain; the
        # chain forms from conv2 on.
        first = next(nid for nid in g.topo_order()
                     if g.nodes[nid].attrs.get("first"))
        assert first not in chain.node_ids
        assert g.nodes[chain.head].inputs == (first,)
        assert len(chain.stages) == 3          # the fused pool decomposes
        assert chain.plan.fits()
        assert chain.hbm_bytes_avoided() > 0

    def test_executor_regions_bit_exact_no_retrace(self, net):
        g, x, ref = net
        ex = chain_executor(g, x.shape)
        np.testing.assert_array_equal(np.asarray(ex(x)), ref)
        n = ex.trace_count
        ex(x)
        ex(x)
        assert ex.trace_count == n == 1
        rows = [r for r in ex.backend_report() if r["op"] == "chain"]
        assert rows and rows[0]["backend"] == "vpu_chain"

    def test_budget_splits_chain_and_stays_exact(self, net):
        """A tiny budget forces the run to split into shorter regions —
        the cut boundaries spill to HBM, results unchanged."""
        g, x, ref = net
        full = partition_chains(g, x.shape)[0]
        budget = full.plan.total_bytes() - 1
        chains = partition_chains(g, x.shape, vmem_budget=budget,
                                  min_nodes=1)
        assert len(chains) > 1
        assert all(c.plan.total_bytes() <= budget for c in chains)
        ex = GraphExecutor(g, "vpu_chain", regions=chains)
        np.testing.assert_array_equal(np.asarray(ex(x)), ref)

    def test_explicit_split_points_stay_exact(self, net):
        """build_chain at arbitrary boundaries (the fuzz axis's tool)."""
        g, x, ref = net
        ids = partition_chains(g, x.shape)[0].node_ids
        for cut in range(1, len(ids)):
            chains = [build_chain(g, ids[:cut], x.shape),
                      build_chain(g, ids[cut:], x.shape)]
            ex = GraphExecutor(g, "vpu_chain", regions=chains)
            np.testing.assert_array_equal(np.asarray(ex(x)), ref,
                                          err_msg=f"split at {cut}")

    def test_fanout_breaks_chain(self):
        """A branching consumer forces materialization: the branch point
        may head a region but never sit inside one."""
        g, packed = _fused_graph(CHAIN_NET, (16, 16), seed=1)
        chains = partition_chains(g, (1, 16, 16, 3))
        mid = chains[0].node_ids[0]
        # add a second consumer of `mid`
        g.add("or_pool", [mid], attrs=dict(window=2, stride=2,
                                           channels=g.nodes[mid]
                                           .attrs["channels"]))
        chains2 = partition_chains(g, (1, 16, 16, 3), min_nodes=1)
        for c in chains2:
            assert mid not in c.node_ids[:-1], c.node_ids

    def test_overlapping_regions_rejected(self, net):
        g, x, _ = net
        ids = partition_chains(g, x.shape)[0].node_ids
        a = build_chain(g, ids[:2], x.shape)
        b = build_chain(g, ids[1:], x.shape)
        with pytest.raises(ValueError, match="overlap"):
            GraphExecutor(g, "vpu_chain", regions=[a, b])

    def test_vmem_plan_invariants(self):
        plan = vmem_plan([1000, 2000, 3000, 500], budget=10_000,
                         fixed_bytes=100)
        # adjacent lifetimes overlap -> disjoint; i and i+2 may share
        for i in range(len(plan.offsets) - 1):
            a = (plan.offsets[i], plan.offsets[i] + plan.sizes[i])
            b = (plan.offsets[i + 1],
                 plan.offsets[i + 1] + plan.sizes[i + 1])
            assert a[1] <= b[0] or b[1] <= a[0]
        assert plan.arena_bytes < plan.naive_bytes()
        assert plan.total_bytes() == plan.arena_bytes + 100
        assert plan.fits()
        assert not vmem_plan([2 ** 24], budget=2 ** 20).fits()

    def test_nonpacked_maxpool_not_chainable(self):
        g = runtime.Graph(input_hw=(8, 8))
        inp = g.add("input", attrs=dict(channels=3))
        g.input_id = inp
        mp = g.add("maxpool_pm1", [inp], attrs=dict(window=2, stride=2,
                                                    channels=3))
        g.output_id = mp
        assert not regions_mod._chainable(g, mp)


# --------------------------------------------------------------------------
# Engine + serving integration
# --------------------------------------------------------------------------

class TestEngineChainMode:

    def test_engine_vpu_chain_cross_check(self):
        spec = CHAIN_NET + [BDense(4 * 4 * 32, 32), FloatDense(32, 10)]
        params = _randomize_bn(
            bnn_model.init_params(jax.random.key(4), spec), seed=9)
        engine = PhoneBitEngine.from_trained(params, spec, (16, 16),
                                             matmul_mode="vpu_chain")
        rng = np.random.default_rng(6)
        x = jnp.asarray(rng.integers(0, 256, (2, 16, 16, 3)), jnp.uint8)
        engine.cross_check(x)  # asserts graph == legacy flat internally
        assert any(r["op"] == "chain" for r in engine.backend_choices)

    def test_served_buckets_chain_zero_retrace(self):
        """The serve path with regions enabled: every bucket bit-exact vs
        the cross_check oracle, trace_count flat while requests flow."""
        from tests import harness

        wl = harness.conformance_workload("yolov2_tiny_voc",
                                          matmul_mode="vpu_chain")
        harness.sweep_served_buckets(wl, buckets=(1, 2), n_requests=3)


# --------------------------------------------------------------------------
# Chain autotune: tile sweep + chain-shaped signature persistence
# --------------------------------------------------------------------------

class TestChainAutotune:

    def test_tile_winner_cached_and_exact(self, monkeypatch, tmp_path):
        monkeypatch.setenv("REPRO_AUTOTUNE_CACHE",
                           str(tmp_path / "tune.json"))
        g, _ = _fused_graph(CHAIN_NET, (16, 16), seed=1)
        rng = np.random.default_rng(2)
        x = jnp.asarray(rng.integers(0, 256, (1, 16, 16, 3)), jnp.uint8)
        ref = np.asarray(GraphExecutor(g, "xla")(x))

        from repro.obs import metrics as obs_metrics

        chains = partition_chains(g, x.shape)
        tuner = Autotuner(warmup=0, iters=1)
        with obs_metrics.use_registry() as reg:
            tuner.tune_chains(g, chains)
        keys = [k for k in tuner.cache if k.startswith("chain::")]
        assert len(keys) == len(chains) == 1
        # one structured miss event per freshly swept chain signature
        evs = reg.events("autotune")
        assert [e["outcome"] for e in evs] == ["miss"]
        assert evs[0]["op"] == "chain" and evs[0]["signature"] == keys[0]
        assert evs[0]["sweep_size"] >= 1
        assert reg.counter("autotune.miss").value == 1
        entry = tuner.cache[keys[0]]
        assert entry["winner"] == "vpu_chain"
        assert any(lbl.startswith("vpu_chain")
                   for lbl in entry["timings_ms"])

        # winner tile executes bit-exactly through the executor
        ex = GraphExecutor(g, "vpu_chain", regions=chains)
        np.testing.assert_array_equal(np.asarray(ex(x)), ref)

        # a fresh tuner warm-starts from disk: no re-timing
        tuner2 = Autotuner(warmup=0, iters=1)
        calls = []
        monkeypatch.setattr(
            Autotuner, "_tune_chain",
            lambda self, c, g: calls.append(c) or {"winner": "vpu_chain",
                                                   "tile": {}})
        chains2 = partition_chains(g, x.shape)
        with obs_metrics.use_registry() as reg2:
            tuner2.tune_chains(g, chains2)
        assert not calls, "disk-cached chain winner was re-timed"
        assert chains2[0].tile == chains[0].tile
        assert reg2.counter("autotune.disk_hit").value == 1
        assert reg2.counter("autotune.miss").value == 0

    def test_candidates_respect_budget(self):
        g, _ = _fused_graph(CHAIN_NET, (16, 16), seed=1)
        chain = partition_chains(g, (1, 16, 16, 3))[0]
        from repro.runtime.autotune import _chain_tile_candidates

        cands = _chain_tile_candidates(chain)
        assert {} in cands and len(cands) >= 2
        for tile in cands:
            assert regions_mod.plan_chain(
                chain.stages, chain.in_shape, tile,
                chain.plan.budget).vmem.fits()


# --------------------------------------------------------------------------
# Memory-plan report regression (satellite): pool-fused outputs count
# against the *producing* node's schedule index, not the consumer's
# --------------------------------------------------------------------------

class TestMemoryReportBirth:

    def test_births_match_hand_schedule(self):
        g, _ = _fused_graph(CHAIN_NET, (16, 16), seed=1)
        schedule = g.topo_order()
        pos = {nid: i for i, nid in enumerate(schedule)}
        cons = g.consumers()
        plan = plan_memory(g, (1, 16, 16, 3))
        fused = [b for b in plan.buffers.values()
                 if b.op == "packed_conv_pool"]
        assert fused, "expected pool-fused intermediates in the plan"
        for b in plan.buffers.values():
            assert b.birth == pos[b.node_id], (
                f"{b.op} (node {b.node_id}) born at {b.birth}, "
                f"produced at schedule index {pos[b.node_id]}")
            assert b.death == max(pos[u] for u in cons[b.node_id])
        # and report() rows carry the same indices
        by_node = {r["node"]: r for r in plan.report()}
        for b in plan.buffers.values():
            assert by_node[b.node_id]["birth"] == pos[b.node_id]
