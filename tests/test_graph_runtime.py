"""Graph runtime: IR lowering, passes, memory planner, executor, autotune,
engine integration, and artifact→graph round-trips (DESIGN.md §4)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import bnn_model, converter, packing
from repro.core.bnn_model import BConv, BDense, FloatConv, FloatDense, Pool
from repro import runtime
from repro.runtime import (Autotuner, GraphExecutor, Graph, assign_layouts,
                           absorb_pools, default_pipeline, fuse_epilogues,
                           infer_types, integrate_bn, lower_packed,
                           lower_trained, plan_memory)
from repro.serving import PhoneBitEngine


def tiny_net():
    return [
        BConv(c_in=3, c_out=16, kernel=3, stride=1, pad=1, first=True),
        Pool(window=2, stride=2),
        BConv(c_in=16, c_out=40, kernel=3, stride=1, pad=1),
        Pool(window=2, stride=2),
        BDense(d_in=4 * 4 * 40, d_out=64),
        FloatDense(d_in=64, d_out=10),
    ]


def conv_net():
    """≥6-layer all-conv net with a stride-1 padded pool (YOLO-style) and a
    float-conv head — exercises pool padding and the unpack→conv tail."""
    return [
        BConv(c_in=3, c_out=16, kernel=3, stride=1, pad=1, first=True),
        Pool(window=2, stride=2),
        BConv(c_in=16, c_out=32, kernel=3, stride=1, pad=1),
        BConv(c_in=32, c_out=32, kernel=3, stride=1, pad=1),
        Pool(window=2, stride=1, pad=(0, 1)),
        BConv(c_in=32, c_out=48, kernel=3, stride=1, pad=1),
        FloatConv(c_in=48, c_out=8, kernel=1, stride=1, pad=0),
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
def tiny():
    spec = tiny_net()
    params = _randomize_bn(bnn_model.init_params(jax.random.key(0), spec))
    packed = converter.convert(params, spec, (16, 16))
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.integers(0, 256, (3, 16, 16, 3)), jnp.uint8)
    return spec, params, packed, x


@pytest.fixture(scope="module")
def convy():
    spec = conv_net()
    params = _randomize_bn(bnn_model.init_params(jax.random.key(1), spec),
                           seed=5)
    packed = converter.convert(params, spec, (16, 16))
    rng = np.random.default_rng(8)
    x = jnp.asarray(rng.integers(0, 256, (2, 16, 16, 3)), jnp.uint8)
    return spec, params, packed, x


# --------------------------------------------------------------------------
# IR + lowering
# --------------------------------------------------------------------------

class TestGraphIR:

    def test_lower_packed_structure(self, tiny):
        spec, _, packed, _ = tiny
        g = lower_packed(spec, packed, (16, 16))
        ops = [g.nodes[i].op for i in g.topo_order()]
        assert ops == ["input", "bitplane_expand", "packed_conv", "or_pool",
                       "packed_conv", "or_pool", "packed_dense",
                       "unpack_pm1", "float_dense"]

    def test_topo_order_is_deterministic_and_valid(self, tiny):
        spec, _, packed, _ = tiny
        g = lower_packed(spec, packed, (16, 16))
        order = g.topo_order()
        assert order == g.topo_order()
        pos = {nid: i for i, nid in enumerate(order)}
        for node in g.nodes.values():
            for src in node.inputs:
                assert pos[src] < pos[node.id]

    def test_cycle_detection(self):
        g = Graph()
        a = g.add("input", attrs=dict(channels=3))
        b = g.add("or_pool", [a], attrs=dict(window=2, stride=2,
                                             channels=3))
        g.nodes[a].inputs = (b,)  # manufacture a cycle
        g.input_id, g.output_id = a, b
        with pytest.raises(ValueError):
            g.topo_order()

    def test_infer_types_matches_execution(self, tiny):
        spec, _, packed, x = tiny
        g = lower_packed(spec, packed, (16, 16))
        types = infer_types(g, x.shape)
        ex = GraphExecutor(g, "xla")
        # run an unjitted pass collecting actual shapes
        env = {}
        for nid in g.topo_order():
            node = g.nodes[nid]
            if node.op == "input":
                env[nid] = x
            else:
                from repro.runtime.executor import eval_node
                env[nid] = eval_node(node.op, node.attrs, node.params,
                                     [env[i] for i in node.inputs])
            assert tuple(env[nid].shape) == types[nid].shape, node.op
            assert env[nid].dtype == types[nid].dtype, node.op


# --------------------------------------------------------------------------
# Executor: bit-exactness across backends, flat path, float oracle
# --------------------------------------------------------------------------

class TestExecutor:

    @pytest.mark.parametrize("backend", ["xla", "xla_pm1", "mxu_pm1"])
    def test_fused_graph_matches_flat_path(self, tiny, backend):
        spec, _, packed, x = tiny
        g = lower_packed(spec, packed, (16, 16))
        got = GraphExecutor(g, backend)(x)
        ref = bnn_model.packed_forward(packed, spec, x)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))

    def test_fused_graph_matches_flat_path_pallas(self, tiny):
        spec, _, packed, x = tiny
        g = lower_packed(spec, packed, (16, 16))
        got = GraphExecutor(g, "vpu_popcount")(x[:1])
        ref = bnn_model.packed_forward(packed, spec, x[:1])
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))

    def test_graph_matches_float_oracle(self, tiny):
        spec, params, packed, x = tiny
        g = lower_packed(spec, packed, (16, 16))
        got = GraphExecutor(g, "xla")(x)
        ref = bnn_model.float_forward(params, spec, x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=0, atol=1e-3)

    def test_conv_head_net_all_backends(self, convy):
        spec, _, packed, x = convy
        g = lower_packed(spec, packed, (16, 16))
        ref = bnn_model.packed_forward(packed, spec, x)
        for backend in ("xla", "xla_pm1"):
            got = GraphExecutor(g, backend)(x)
            np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))

    def test_no_retrace_on_repeat_calls(self, tiny):
        spec, _, packed, x = tiny
        ex = GraphExecutor(lower_packed(spec, packed, (16, 16)), "xla")
        ex(x)
        assert ex.trace_count == 1
        ex(x)
        ex(x)
        assert ex.trace_count == 1

    def test_branching_graph_concat(self):
        """Two parallel conv branches concat'd — inexpressible as a flat
        LayerSpec list; cross-checked against manual composition."""
        rng = np.random.default_rng(3)
        spec1 = [BConv(3, 32, 3, 1, 1, first=True)]
        spec2 = [BConv(3, 64, 3, 1, 1, first=True)]
        p1 = _randomize_bn(bnn_model.init_params(jax.random.key(2), spec1))
        p2 = _randomize_bn(bnn_model.init_params(jax.random.key(3), spec2))
        pk1 = converter.convert(p1, spec1, (8, 8))
        pk2 = converter.convert(p2, spec2, (8, 8))
        x = jnp.asarray(rng.integers(0, 256, (2, 8, 8, 3)), jnp.uint8)

        g = Graph(input_hw=(8, 8))
        inp = g.add("input", attrs=dict(channels=3))
        g.input_id = inp
        bp = g.add("bitplane_expand", [inp], attrs=dict(c_in=3, channels=3))
        conv_attrs = dict(kernel=3, stride=1, pad=1, first=True)
        b1 = g.add("packed_conv", [bp],
                   attrs=dict(channels=32, **conv_attrs),
                   params=dict(w_packed=pk1[0]["w_packed"],
                               thresh=pk1[0]["thresh"],
                               word_weights=pk1[0]["word_weights"]))
        b2 = g.add("packed_conv", [bp],
                   attrs=dict(channels=64, **conv_attrs),
                   params=dict(w_packed=pk2[0]["w_packed"],
                               thresh=pk2[0]["thresh"],
                               word_weights=pk2[0]["word_weights"]))
        cat = g.add("concat_packed", [b1, b2], attrs=dict(channels=96))
        g.output_id = cat
        got = GraphExecutor(g, "xla")(x)

        r1 = bnn_model.packed_forward(pk1, spec1, x)
        r2 = bnn_model.packed_forward(pk2, spec2, x)
        ref = jnp.concatenate([r1, r2], axis=-1)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


# --------------------------------------------------------------------------
# Passes
# --------------------------------------------------------------------------

class TestPasses:

    def test_layout_pass_inserts_adapters(self, tiny):
        spec, params, _, _ = tiny
        g = lower_trained(spec, params, (16, 16))
        ops_before = {n.op for n in g.nodes.values()}
        assert "bitplane_expand" not in ops_before
        assert "unpack_pm1" not in ops_before
        g2 = assign_layouts(g)
        ops_after = [g2.nodes[i].op for i in g2.topo_order()]
        assert "bitplane_expand" in ops_after
        assert "unpack_pm1" in ops_after
        # adapters are wired, not appended: expand feeds the first conv
        for node in g2.nodes.values():
            if node.op == "conv_counts" and node.attrs["first"]:
                assert g2.nodes[node.inputs[0]].op == "bitplane_expand"

    def test_unfused_graph_matches_float_oracle(self, tiny):
        spec, params, _, x = tiny
        g = assign_layouts(lower_trained(spec, params, (16, 16)))
        got = GraphExecutor(g, "xla")(x)
        ref = bnn_model.float_forward(params, spec, x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=0, atol=1e-3)

    def test_integrate_bn_is_exact(self, tiny):
        spec, params, _, x = tiny
        g = assign_layouts(lower_trained(spec, params, (16, 16)))
        gi = integrate_bn(g)
        assert all(n.op != "bn_binarize" for n in gi.nodes.values())
        np.testing.assert_array_equal(
            np.asarray(GraphExecutor(g, "xla")(x)),
            np.asarray(GraphExecutor(gi, "xla")(x)))

    def test_fuse_epilogues(self, tiny):
        spec, params, _, x = tiny
        g = integrate_bn(assign_layouts(lower_trained(spec, params,
                                                      (16, 16))))
        gf = fuse_epilogues(g)
        ops = {n.op for n in gf.nodes.values()}
        assert "conv_counts" not in ops and "threshold_pack" not in ops
        assert "packed_conv" in ops and "packed_dense" in ops
        np.testing.assert_array_equal(
            np.asarray(GraphExecutor(g, "xla")(x)),
            np.asarray(GraphExecutor(gf, "xla")(x)))

    def test_absorb_pools(self, tiny):
        spec, params, _, x = tiny
        g = fuse_epilogues(integrate_bn(assign_layouts(
            lower_trained(spec, params, (16, 16)))))
        ga = absorb_pools(g)
        assert all(n.op != "maxpool_pm1" for n in ga.nodes.values())
        assert any(n.op == "or_pool" for n in ga.nodes.values())
        np.testing.assert_array_equal(
            np.asarray(GraphExecutor(g, "xla")(x)),
            np.asarray(GraphExecutor(ga, "xla")(x)))

    def test_pipeline_converges_to_artifact_lowering(self, tiny):
        """lower_trained + passes == lower_packed(converter.convert(...))."""
        spec, params, packed, x = tiny
        g_pass = default_pipeline(lower_trained(spec, params, (16, 16)))
        g_art = lower_packed(spec, packed, (16, 16))
        assert ([g_pass.nodes[i].op for i in g_pass.topo_order()] ==
                [g_art.nodes[i].op for i in g_art.topo_order()])
        np.testing.assert_array_equal(
            np.asarray(GraphExecutor(g_pass, "xla")(x)),
            np.asarray(GraphExecutor(g_art, "xla")(x)))

    def test_pipeline_on_conv_head_net(self, convy):
        spec, params, packed, x = convy
        g_pass = default_pipeline(lower_trained(spec, params, (16, 16)))
        ref = bnn_model.packed_forward(packed, spec, x)
        np.testing.assert_array_equal(
            np.asarray(GraphExecutor(g_pass, "xla")(x)), np.asarray(ref))


# --------------------------------------------------------------------------
# Memory planner
# --------------------------------------------------------------------------

class TestMemoryPlanner:

    def test_reuse_beats_naive_on_deep_net(self, convy):
        spec, _, packed, x = convy
        g = lower_packed(spec, packed, (16, 16))
        assert len([l for l in spec if isinstance(l, (BConv, FloatConv))]) >= 5
        plan = plan_memory(g, x.shape)
        assert plan.peak_bytes() < plan.naive_bytes()
        assert plan.peak_bytes() >= plan.live_peak_bytes() > 0

    def test_no_overlap_for_live_buffers(self, convy):
        spec, _, packed, x = convy
        g = lower_packed(spec, packed, (16, 16))
        plan = plan_memory(g, x.shape)
        bufs = list(plan.buffers.values())
        for i, a in enumerate(bufs):
            for b in bufs[i + 1:]:
                lifetimes_overlap = not (a.death < b.birth or
                                         b.death < a.birth)
                space_overlap = not (a.offset + a.nbytes <= b.offset or
                                     b.offset + b.nbytes <= a.offset)
                assert not (lifetimes_overlap and space_overlap), (a, b)

    def test_arena_bounded_by_two_largest(self, tiny):
        """For a pure chain, peak is at most the two largest adjacent
        buffers (producer + consumer live simultaneously)."""
        spec, _, packed, x = tiny
        g = lower_packed(spec, packed, (16, 16))
        plan = plan_memory(g, x.shape)
        sizes = sorted((b.nbytes for b in plan.buffers.values()),
                       reverse=True)
        assert plan.peak_bytes() <= sizes[0] + sizes[1]

    def test_report_rows(self, tiny):
        spec, _, packed, x = tiny
        plan = plan_memory(lower_packed(spec, packed, (16, 16)), x.shape)
        rows = plan.report()
        assert rows and all(
            {"node", "op", "bytes", "offset", "birth", "death"} <= set(r)
            for r in rows)


# --------------------------------------------------------------------------
# Autotune
# --------------------------------------------------------------------------

class TestAutotune:

    def test_selects_caches_and_stays_exact(self, tiny):
        spec, _, packed, x = tiny
        g = lower_packed(spec, packed, (16, 16))
        cache = {}
        tuner = Autotuner(cache=cache, candidates=("xla", "xla_pm1"),
                          warmup=1, iters=1)
        choices = tuner.tune(g, x.shape)
        # The first conv takes the MXU lowering by rule, untimed; every
        # other node is timed among the candidates and cached.
        timed = {nid: b for nid, b in choices.items()
                 if b != runtime.IMAGE_BACKEND}
        assert choices[2] == runtime.IMAGE_BACKEND
        assert timed and all(b in ("xla", "xla_pm1")
                             for b in timed.values())
        assert len(cache) == len(timed)
        # second tune hits the cache (no new entries, same winners)
        assert tuner.tune(g, x.shape) == choices
        assert len(cache) == len(timed)
        ex = GraphExecutor(g, choices)
        ref = bnn_model.packed_forward(packed, spec, x)
        np.testing.assert_array_equal(np.asarray(ex(x)), np.asarray(ref))

    def test_no_recompile_at_serve_time(self, tiny):
        spec, _, packed, x = tiny
        g = lower_packed(spec, packed, (16, 16))
        tuner = Autotuner(candidates=("xla", "xla_pm1"), warmup=1, iters=1)
        ex = tuner.tuned_executor(g, x.shape)
        ex(x)
        n = ex.trace_count
        for _ in range(3):
            ex(x)
        assert ex.trace_count == n == 1


# --------------------------------------------------------------------------
# Engine integration + artifact round-trips (satellites)
# --------------------------------------------------------------------------

class TestEngineGraphPath:

    def test_engine_runs_graph_and_matches_legacy(self, tiny, tmp_path):
        spec, params, _, x = tiny
        for mode in ("xla", "xla_pm1"):
            engine = PhoneBitEngine.from_trained(params, spec, (16, 16),
                                                 matmul_mode=mode)
            engine.cross_check(x)  # asserts graph == flat internally

    def test_engine_prepare_is_explicit_and_order_independent(self, tiny):
        spec, params, _, x = tiny
        e1 = PhoneBitEngine.from_trained(params, spec, (16, 16))
        arrays, meta = e1.prepare()  # before any inference
        assert len(arrays) == len(meta) == len(spec)
        assert all("c_per_pos" not in a for a in arrays)
        assert any("c_per_pos" in m for m in meta)
        out1 = e1(x)
        # calling prepare() after inference gives the same split
        arrays2, meta2 = e1.prepare()
        assert meta2 == meta
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)), arrays, arrays2)
        # inference-first engine agrees with prepare-first engine
        e2 = PhoneBitEngine.from_trained(params, spec, (16, 16))
        out2 = e2(x)
        np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))

    def test_engine_memory_plan_and_backends(self, tiny):
        spec, params, _, x = tiny
        engine = PhoneBitEngine.from_trained(params, spec, (16, 16),
                                             batch_size=3)
        plan = engine.memory_plan()
        assert plan.peak_bytes() < plan.naive_bytes()
        assert all(r["backend"] == "xla" for r in engine.backend_choices)

    def test_engine_autotune_mode(self, tiny):
        spec, params, _, x = tiny
        engine = PhoneBitEngine.from_trained(params, spec, (16, 16),
                                             matmul_mode="auto",
                                             batch_size=3)
        engine.cross_check(x)
        assert all(r["backend"] in runtime.BACKENDS
                   + (runtime.IMAGE_BACKEND,)
                   for r in engine.backend_choices)
        assert engine.backend_choices[0]["backend"] == runtime.IMAGE_BACKEND

    def test_artifact_graph_roundtrip_all_backends(self, tiny, tmp_path):
        """save_artifact → load_artifact → graph lowering → executor is
        bit-exact vs the legacy flat path and the float oracle."""
        spec, params, packed, x = tiny
        path = str(tmp_path / "m.npz")
        converter.save_artifact(path, packed)
        loaded = converter.load_artifact(path)
        g = converter.to_graph(loaded, spec, (16, 16))
        flat_ref = bnn_model.packed_forward(packed, spec, x)
        float_ref = bnn_model.float_forward(params, spec, x)
        for backend in ("xla", "xla_pm1"):
            got = np.asarray(GraphExecutor(g, backend)(x))
            np.testing.assert_array_equal(got, np.asarray(flat_ref))
            np.testing.assert_allclose(got, np.asarray(float_ref),
                                       rtol=0, atol=1e-3)
        # Same batch as the reference: XLA's float head may round the
        # last ulp differently at another batch size.
        got = np.asarray(GraphExecutor(g, "vpu_popcount")(x[:1]))
        np.testing.assert_array_equal(
            got, np.asarray(bnn_model.packed_forward(packed, spec, x[:1])))

    def test_core_to_graph_hooks(self, tiny):
        spec, params, packed, x = tiny
        ga = converter.to_graph(packed, spec, (16, 16))
        gt = default_pipeline(bnn_model.to_graph(params, spec, (16, 16)))
        np.testing.assert_array_equal(
            np.asarray(GraphExecutor(ga, "xla")(x)),
            np.asarray(GraphExecutor(gt, "xla")(x)))


# --------------------------------------------------------------------------
# Differential backend fuzz (workload-conformance satellite)
# --------------------------------------------------------------------------

def _random_spec(rng: np.random.Generator) -> tuple[list, int]:
    """A random small-but-legal network: bit-plane first conv, 1-3 hidden
    packed conv blocks (random kernel/channels, optional pool), then
    either a packed-dense + float-dense tail or a 1x1 float-conv head.
    Returns (spec, input_hw)."""
    hw0 = hw = int(rng.choice([8, 16]))
    c = int(rng.choice([16, 24, 32]))
    spec = [BConv(3, c, kernel=3, stride=1, pad=1, first=True)]
    for _ in range(int(rng.integers(1, 4))):
        kernel = int(rng.choice([1, 3]))
        c_out = int(rng.choice([16, 32, 40]))
        spec.append(BConv(c, c_out, kernel=kernel, stride=1,
                          pad=kernel // 2))
        c = c_out
        if hw >= 8 and rng.random() < 0.5:
            spec.append(Pool(2, 2))
            hw //= 2
    if rng.random() < 0.5:
        spec.append(BDense(hw * hw * c, 32))
        spec.append(FloatDense(32, 10))
    else:
        spec.append(FloatConv(c, 8, kernel=1, stride=1, pad=0))
    return spec, hw0


class TestDifferentialFuzz:
    """Random graph specs executed on every valid backend, asserting
    bit-exactness pairwise (via the shared xla reference — equality is
    transitive).  The Pallas backends run in interpret mode off-TPU, so
    shapes stay small."""

    @given(st.integers(0, 10**9))
    @settings(max_examples=4, deadline=None)
    def test_random_spec_all_backend_pairs(self, seed):
        rng = np.random.default_rng(seed)
        spec, hw0 = _random_spec(rng)
        params = _randomize_bn(
            bnn_model.init_params(jax.random.key(seed % (2**31)), spec),
            seed=seed % 7919)
        packed = converter.convert(params, spec, (hw0, hw0))
        # Pool-fused graph so conv+pool pairs exercise packed_conv_pool
        # (every backend accepts it; string modes degrade where needed).
        g = runtime.fuse_pool_epilogue(lower_packed(spec, packed,
                                                    (hw0, hw0)))
        x = jnp.asarray(rng.integers(0, 256, (2, hw0, hw0, 3)), jnp.uint8)
        ref = np.asarray(GraphExecutor(g, "xla")(x))
        np.testing.assert_array_equal(        # graph == flat oracle
            ref, np.asarray(bnn_model.packed_forward(packed, spec, x)))
        for backend in ("xla_pm1", "mxu_pm1"):
            np.testing.assert_array_equal(
                np.asarray(GraphExecutor(g, backend)(x)), ref,
                err_msg=f"{backend} diverges on spec {spec}")
        # interpret-mode Pallas backends: batch 1 keeps them fast; the
        # reference runs at the same batch (XLA's float head may round
        # the last ulp differently at another batch size)
        ref1 = np.asarray(GraphExecutor(g, "xla")(x[:1]))
        for backend in ("vpu_popcount", "vpu_direct", "vpu_direct_pool"):
            got = np.asarray(GraphExecutor(g, backend)(x[:1]))
            np.testing.assert_array_equal(
                got, ref1, err_msg=f"{backend} diverges on spec {spec}")

    @given(st.integers(0, 10**9))
    @settings(max_examples=4, deadline=None)
    def test_random_chain_splits_bit_exact(self, seed):
        """Chain-fusion axis (DESIGN.md §9): the same random graphs, but
        executed through megakernel regions split at *random* chain
        boundaries — every split must stay bit-exact vs the per-node xla
        reference (each cut boundary spills to HBM; the fused interiors
        live in the VMEM arena)."""
        rng = np.random.default_rng(seed)
        spec, hw0 = _random_spec(rng)
        params = _randomize_bn(
            bnn_model.init_params(jax.random.key(seed % (2**31)), spec),
            seed=seed % 7919)
        packed = converter.convert(params, spec, (hw0, hw0))
        g = runtime.fuse_pool_epilogue(lower_packed(spec, packed,
                                                    (hw0, hw0)))
        x = jnp.asarray(rng.integers(0, 256, (1, hw0, hw0, 3)), jnp.uint8)
        ref = np.asarray(GraphExecutor(g, "xla")(x))

        split = []
        for chain in runtime.partition_chains(g, x.shape, min_nodes=1):
            ids = chain.node_ids
            cuts = {0, len(ids)}
            if len(ids) > 1:
                cuts.update(int(rng.integers(1, len(ids)))
                            for _ in range(int(rng.integers(0, 3))))
            cuts = sorted(cuts)
            split += [runtime.build_chain(g, ids[a:b], x.shape)
                      for a, b in zip(cuts, cuts[1:])]
        assert split, f"no chainable run in spec {spec}"
        ex = GraphExecutor(g, "vpu_chain", regions=split)
        np.testing.assert_array_equal(
            np.asarray(ex(x)), ref,
            err_msg=f"chain split {[c.node_ids for c in split]} diverges "
                    f"on spec {spec}")

    # ---- forced-mesh placement sweeps (DESIGN.md §13) --------------------
    # Placement is a backend choice like any other: the same random specs
    # the backend-pair fuzz runs must agree when sharded over a mesh axis
    # or cut into pipeline stages.  Multi-device needs
    # --xla_force_host_platform_device_count, which must be set before
    # jax imports and must never leak into this process — so each sweep
    # runs in one subprocess covering several seeds.  Bar: packed int32
    # tails bit-exact, float heads 1e-4.

    _PLACEMENT_SWEEP = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={n_dev}"
os.environ["REPRO_AUTOTUNE_CACHE"] = "0"
import sys
sys.path.insert(0, {src!r})
sys.path.insert(0, {tests!r})
import jax, jax.numpy as jnp
import numpy as np
from test_graph_runtime import _random_spec, _randomize_bn
from repro.core import bnn_model
from repro.core.bnn_model import BConv, BDense, FloatConv, FloatDense
from repro.serving import PhoneBitEngine

N = {n_dev}
assert len(jax.devices()) == N, jax.devices()
for seed in {seeds}:
    rng = np.random.default_rng(seed)
    spec, hw0 = _random_spec(rng)
    # Two variants per spec: the original float head (1e-4 bar) and a
    # packed-tail derivative (bit-exact bar) — drop a FloatDense tail,
    # swap a FloatConv head for a BDense.
    last = spec[-1]
    if isinstance(last, FloatDense):
        spec_p = spec[:-1]
    else:
        hw_c = last.c_in
        hw_sp = hw0
        for l in spec:
            if type(l).__name__ == "Pool":
                hw_sp //= l.stride
        spec_p = spec[:-1] + [BDense(hw_sp * hw_sp * hw_c, 32)]
    for sp, exact in ((spec, False), (spec_p, True)):
        params = _randomize_bn(
            bnn_model.init_params(jax.random.key(seed % (2**31)), sp),
            seed=seed % 7919)
        engine = PhoneBitEngine.from_trained(params, sp, (hw0, hw0))
        bs = 2 * N
        x = jnp.asarray(rng.integers(0, 256, (bs, hw0, hw0, 3)),
                        jnp.uint8)
        ref = np.asarray(engine.compile(bs)(x))
        # data-parallel: batch dim sharded over the forced mesh
        got_dp = np.asarray(engine.compile(bs, data_parallel=N)(x))
        # pipeline-parallel: schedule cut into per-device stages
        got_pp = np.asarray(engine.compile(
            bs, pipeline=jax.devices())(x))
        # zero-padded bucket traffic (ragged batch padded up)
        pad = np.zeros_like(x)
        pad[: bs // 2] = np.asarray(x[: bs // 2])
        ref_pad = np.asarray(engine.compile(bs)(jnp.asarray(pad)))
        dp_pad = np.asarray(engine.compile(bs, data_parallel=N)(
            jnp.asarray(pad)))
        pp_pad = np.asarray(engine.compile(bs, pipeline=jax.devices())(
            jnp.asarray(pad)))
        for name, got, want in (("dp", got_dp, ref),
                                ("pp", got_pp, ref),
                                ("dp-pad", dp_pad, ref_pad),
                                ("pp-pad", pp_pad, ref_pad)):
            if exact:
                np.testing.assert_array_equal(
                    got, want, err_msg=f"{{name}} seed={{seed}} {{sp}}")
            else:
                np.testing.assert_allclose(
                    got, want, atol=1e-4,
                    err_msg=f"{{name}} seed={{seed}} {{sp}}")
    print("seed", seed, "ok")
print("placement-fuzz-ok")
"""

    @pytest.mark.parametrize("n_dev", [2, 4])
    def test_placement_parity_sweep_forced_mesh(self, n_dev):
        import pathlib
        import subprocess
        import sys as _sys

        tests = pathlib.Path(__file__).resolve().parent
        rng = np.random.default_rng(1000 + n_dev)
        seeds = [int(s) for s in rng.integers(0, 10**9, 3)]
        script = self._PLACEMENT_SWEEP.format(
            n_dev=n_dev, src=str(tests.parent / "src"),
            tests=str(tests), seeds=seeds)
        r = subprocess.run([_sys.executable, "-c", script],
                           capture_output=True, text=True, timeout=560)
        assert r.returncode == 0, \
            f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
        assert "placement-fuzz-ok" in r.stdout
