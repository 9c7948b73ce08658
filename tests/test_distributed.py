"""Multi-device scale-out tests on the runtime-IR placement surface
(DESIGN.md §13).

Two tiers:

* **In-process (1 device)** — the placement pass is pure graph math
  and the staged executor runs fine with every stage on one device, so
  cut-candidate/plan properties, stage-subgraph parity, replica
  routing, and straggler deprioritization are all pinned inside the
  normal tier-1 run.
* **Forced-mesh subprocesses** — scenarios that need real multiple
  devices run in a SUBPROCESS with
  ``--xla_force_host_platform_device_count=N``: the placeholder-device
  flag must never leak into this pytest process (smoke tests see 1
  device, per the dry-run contract).  Scenarios assert internally and
  exit non-zero on failure.  The parity bar matches the backend-pair
  fuzz: packed int32 tails bit-exact, float heads 1e-4 (placement —
  like backend choice — may change XLA fusion and thus last-ulp float
  accumulation, never the packed computation).
"""

import os
import pathlib
import subprocess
import sys
import textwrap

import numpy as np
import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent

_PRELUDE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={n_dev}"
os.environ["REPRO_AUTOTUNE_CACHE"] = "0"
import sys; sys.path.insert(0, {src!r})
import jax, jax.numpy as jnp
import numpy as np
"""

# The tiny nets the serving tests standardize on: a float head (logits)
# and a packed tail (int32 words — the bit-exact surface).
_ENGINES = """
from repro.core import bnn_model
from repro.core.bnn_model import BConv, BDense, FloatDense, Pool
from repro.serving import PhoneBitEngine

def tiny_engine(tail="float"):
    if tail == "float":
        spec = [BConv(3, 32, kernel=3, stride=1, pad=1, first=True),
                Pool(2, 2), FloatDense(8 * 8 * 32, 10)]
    else:
        spec = [BConv(3, 32, kernel=3, stride=1, pad=1, first=True),
                BConv(32, 32, kernel=3, stride=1, pad=1),
                Pool(2, 2), BDense(8 * 8 * 32, 64)]
    params = bnn_model.init_params(jax.random.key(0), spec)
    return PhoneBitEngine.from_trained(params, spec, (16, 16))
"""


def _run(body: str, n_dev: int = 8, timeout: int = 420,
         setup: str = "") -> str:
    # setup (unindented module text) and body (indented in the caller)
    # concatenate only after dedent — mixed indents defeat dedent.
    script = (_PRELUDE.format(src=str(REPO / "src"), n_dev=n_dev)
              + setup + textwrap.dedent(body))
    r = subprocess.run([sys.executable, "-c", script],
                       capture_output=True, text=True, timeout=timeout,
                       env=dict(os.environ))
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


# --------------------------------------------------------------------------
# Placement pass: pure graph math, in-process
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def engines():
    import jax

    from repro.core import bnn_model
    from repro.core.bnn_model import BConv, BDense, FloatDense, Pool
    from repro.serving import PhoneBitEngine

    def build(spec):
        params = bnn_model.init_params(jax.random.key(0), spec)
        return PhoneBitEngine.from_trained(params, spec, (16, 16))

    return {
        "float": build([BConv(3, 32, kernel=3, stride=1, pad=1, first=True),
                        Pool(2, 2), FloatDense(8 * 8 * 32, 10)]),
        # conv2+pool2 and conv3 form a chain (the first conv runs on the
        # MXU from the image, outside every chain).
        "packed": build([BConv(3, 32, kernel=3, stride=1, pad=1, first=True),
                         BConv(32, 32, kernel=3, stride=1, pad=1),
                         Pool(2, 2), BConv(32, 32, kernel=3, stride=1, pad=1),
                         BDense(8 * 8 * 32, 64)]),
    }


class TestPlacementPass:
    def test_cut_candidates_are_single_live_crossings(self, engines):
        from repro import runtime

        g = engines["packed"]._graph
        schedule = g.topo_order()
        pos = {nid: i for i, nid in enumerate(schedule)}
        cons = g.consumers()
        cands = runtime.cut_candidates(g)
        assert cands, "a linear BNN graph must offer cut points"
        for i, boundary in cands:
            live = [nid for nid in schedule[:i + 1]
                    if any(pos[c] > i for c in cons[nid])
                    or nid == g.output_id]
            assert live == [boundary]
            # the boundary is the last node of its stage (topo order)
            assert boundary == schedule[i]

    def test_forbidden_interiors_excluded(self, engines):
        from repro import runtime
        from repro.runtime.placement import chain_interiors

        g = engines["packed"]._graph
        chains = runtime.partition_chains(g, (1, 16, 16, 3))
        if not chains:
            pytest.skip("net formed no chains at this budget")
        forbidden = chain_interiors(chains)
        cands = runtime.cut_candidates(g, forbidden)
        for _, boundary in cands:
            assert boundary not in forbidden
        # chain tails stay legal boundaries — they ARE the HBM touch
        # points region formation already identified
        tails = {c.node_ids[-1] for c in chains}
        assert tails & {b for _, b in cands}

    def test_plan_covers_schedule_in_order(self, engines):
        from repro import runtime

        g = engines["float"]._graph
        plan = runtime.plan_pipeline(g, (2, 16, 16, 3), 2)
        flat = [nid for stage in plan.stages for nid in stage]
        assert flat == g.topo_order()
        assert len(plan.boundaries) == plan.n_stages - 1
        for stage, b in zip(plan.stages, plan.boundaries):
            assert b == stage[-1]      # produced by its own stage
        assert len(plan.costs) == plan.n_stages
        assert all(c >= 0 for c in plan.costs)

    def test_plan_degrades_when_graph_offers_fewer_cuts(self, engines):
        from repro import runtime

        g = engines["float"]._graph
        plan = runtime.plan_pipeline(g, (1, 16, 16, 3), 99)
        assert 1 <= plan.n_stages <= len(runtime.cut_candidates(g)) + 1
        assert plan.n_stages < 99

    def test_plan_balances_cost(self, engines):
        from repro import runtime

        g = engines["packed"]._graph
        plan = runtime.plan_pipeline(g, (4, 16, 16, 3), 2)
        if plan.n_stages < 2:
            pytest.skip("no legal 2-stage split")
        # the DP must beat the most lopsided legal split
        worst = sum(plan.costs)
        assert max(plan.costs) < worst
        rep = plan.report()
        assert abs(sum(r["share"] for r in rep) - 1.0) < 1e-6

    def test_stage_subgraphs_validate_and_keep_ids(self, engines):
        from repro import runtime

        g = engines["packed"]._graph
        plan = runtime.plan_pipeline(g, (1, 16, 16, 3), 2)
        sub0 = runtime.stage_subgraph(g, plan.stages[0], None)
        sub1 = runtime.stage_subgraph(g, plan.stages[1],
                                      plan.boundaries[0])
        assert set(sub0.nodes) == set(plan.stages[0])
        assert sub1.input_id == plan.boundaries[0]
        assert sub1.nodes[plan.boundaries[0]].op == "input"
        # intra-stage edges survive untouched (same node ids)
        for nid in plan.stages[1]:
            assert sub1.nodes[nid].inputs == g.nodes[nid].inputs


class TestStagedExecutor:
    def test_packed_tail_bit_exact_vs_single(self, engines):
        import jax

        from repro import runtime

        eng = engines["packed"]
        g = eng._graph
        shape = (4, 16, 16, 3)
        rng = np.random.default_rng(0)
        x = rng.integers(0, 256, shape, dtype=np.uint8)
        ref = np.asarray(runtime.GraphExecutor(g, "xla")(x))
        dev = jax.devices()[0]
        for n_stages in (1, 2, 3):
            exe = runtime.staged_executor(g, shape, (dev,) * n_stages,
                                          mode="xla")
            got = np.asarray(exe(x))
            np.testing.assert_array_equal(got, ref)   # packed: bit-exact

    def test_float_head_matches_cross_check(self, engines):
        import jax

        from repro import runtime

        eng = engines["float"]
        shape = (2, 16, 16, 3)
        rng = np.random.default_rng(1)
        x = rng.integers(0, 256, shape, dtype=np.uint8)
        ref = np.asarray(eng.cross_check(x))
        exe = runtime.staged_executor(eng._graph, shape,
                                      (jax.devices()[0],) * 2, mode="xla")
        np.testing.assert_allclose(np.asarray(exe(x)), ref, atol=1e-4)

    def test_trace_count_and_reports(self, engines):
        import jax

        from repro import runtime

        exe = runtime.staged_executor(engines["float"]._graph,
                                      (2, 16, 16, 3),
                                      (jax.devices()[0],) * 2)
        x = np.zeros((2, 16, 16, 3), np.uint8)
        exe(x)
        t = exe.trace_count
        assert t == exe.plan.n_stages       # one trace per stage
        exe(x); exe(x)
        assert exe.trace_count == t         # serve-time: no retrace
        rows = exe.stage_report()
        assert len(rows) == exe.plan.n_stages
        assert all("device" in r and "share" in r for r in rows)
        assert all("stage" in r for r in exe.backend_report())

    def test_chain_mode_refuses_interior_cuts(self, engines):
        import jax

        from repro import runtime
        from repro.runtime.placement import chain_interiors

        eng = engines["packed"]
        g = eng._graph
        chains = runtime.partition_chains(g, (1, 16, 16, 3))
        if not chains:
            pytest.skip("net formed no chains at this budget")
        forbidden = chain_interiors(chains)
        exe = runtime.StagedExecutor(g, (1, 16, 16, 3),
                                     (jax.devices()[0],) * 2,
                                     mode="vpu_chain")
        for b in exe.plan.boundaries:
            assert b not in forbidden
        x = np.zeros((1, 16, 16, 3), np.uint8)
        ref = np.asarray(runtime.GraphExecutor(g, "xla")(x))
        np.testing.assert_array_equal(np.asarray(exe(x)), ref)


# --------------------------------------------------------------------------
# Replica group: routing + protocol, in-process (1 device)
# --------------------------------------------------------------------------

class TestReplicaGroup:
    def test_serves_bit_exact_and_flat_traces(self, engines):
        import jax

        from repro.distributed import ReplicaGroup

        eng = engines["packed"]
        grp = ReplicaGroup(eng, [jax.devices()[0]] * 2,
                           buckets=(2, 4), max_batch=4)
        grp.compile_buckets()
        before = grp.trace_count
        rng = np.random.default_rng(0)
        imgs = [rng.integers(0, 256, (16, 16, 3), dtype=np.uint8)
                for _ in range(6)]
        reqs = [grp.submit(i) for i in imgs]
        grp.drain()
        assert grp.trace_count == before
        ref = np.asarray(eng(np.stack(imgs)))
        for i, r in enumerate(reqs):
            assert r.outcome == "served"
            np.testing.assert_array_equal(np.asarray(r.result), ref[i])
        m = grp.metrics()
        assert set(m["replicas"]) == {"r0", "r1"}
        assert all(v["healthy"] for v in m["routing"].values())

    def test_routing_prefers_shallow_queues(self, engines):
        import jax

        from repro.distributed import ReplicaGroup

        grp = ReplicaGroup(engines["packed"], [jax.devices()[0]] * 2,
                           buckets=(2, 4), max_batch=4)
        x = np.zeros((16, 16, 3), np.uint8)
        grp.submit(x, replica="r0")
        grp.submit(x, replica="r0")
        assert grp._route().name == "r1"    # depth 0 beats depth 2
        grp.drain()

    def test_slow_replica_deprioritized_then_recovers(self, engines):
        import jax

        from repro.distributed import ReplicaGroup

        grp = ReplicaGroup(engines["packed"], [jax.devices()[0]] * 2,
                           slow_after=2)
        r1 = grp.replicas["r1"]
        # feed the monitor a stable baseline, then persistent outliers
        for i in range(r1.monitor.min_samples):
            grp._observe_step(r1, 0.01, i)
        for i in range(3):
            grp._observe_step(r1, 10.0, 100 + i)
        assert r1.slow and not r1.healthy
        assert grp._route().name == "r0"
        # a clean step clears the flag — the replica rejoins the pool
        grp._observe_step(r1, 0.01, 200)
        assert not r1.slow and r1.healthy

    def test_shape_validation(self, engines):
        import jax

        from repro.distributed import ReplicaGroup

        dev = jax.devices()[0]
        with pytest.raises(ValueError):
            ReplicaGroup(engines["packed"], [dev] * 3,
                         devices_per_replica=2)
        with pytest.raises(ValueError):
            ReplicaGroup(engines["packed"], [dev] * 2, names=("a",))


# --------------------------------------------------------------------------
# Forced-mesh subprocesses: real multi-device placement
# --------------------------------------------------------------------------

def test_pipelined_serving_matches_single_device():
    """4-stage pipeline on a forced 4-device mesh: params committed to
    distinct devices, serving bit-exact vs the single-device oracle
    (packed tail) and 1e-4 (float head), zero serve-time retraces —
    including zero-padded buckets."""
    out = _run(setup=_ENGINES, body="""
    from repro.distributed import Pipelined
    from repro.serving import InferenceServer

    rng = np.random.default_rng(0)
    imgs = [rng.integers(0, 256, (16, 16, 3), dtype=np.uint8)
            for _ in range(7)]                       # 7 -> padded bucket 8

    for tail, exact in (("packed", True), ("float", False)):
        engine = tiny_engine(tail)
        placement = Pipelined.over(4)
        assert placement.n_stages == 4
        piped = InferenceServer(engine, buckets=(2, 4, 8), max_batch=8,
                                placement=placement)
        single = InferenceServer(engine, buckets=(2, 4, 8), max_batch=8)
        piped.compile_buckets(); single.compile_buckets()
        before = engine.trace_count

        # the realized split really spans devices (the plan may merge
        # stages when the graph is short on cut points)
        exe = piped._executable(8)
        devs = {str(d) for d in exe.devices}
        assert len(devs) == exe.plan.n_stages > 1, devs
        for dev, e in zip(exe.devices, exe.stage_executors):
            for a in jax.tree.leaves(e.arrays):   # params committed
                assert {str(d) for d in a.devices()} == {str(dev)}

        rp = [piped.submit(i) for i in imgs]
        rs = [single.submit(i) for i in imgs]
        piped.drain(); single.drain()
        assert engine.trace_count == before     # serve-time: no retrace
        for a, b in zip(rp, rs):
            assert a.outcome == b.outcome == "served"
            if exact:
                np.testing.assert_array_equal(a.result, b.result)
            else:
                np.testing.assert_allclose(a.result, b.result, atol=1e-4)
        # oracle: the flat packed_forward walk, single device
        ref = np.asarray(engine.cross_check(jnp.asarray(np.stack(imgs))))
        for i, a in enumerate(rp):
            np.testing.assert_allclose(a.result, ref[i], atol=1e-4)
        m = piped.metrics()
        assert m["placement"]["kind"] == "pipeline"
        assert len(m["placement"]["devices"]) == 4
    print("pipelined-parity-ok")
    """, n_dev=4)
    assert "pipelined-parity-ok" in out


def test_replica_group_forced_mesh_parity():
    """4 one-device replicas on a forced mesh: params pinned per
    replica device, group serving bit-exact vs the oracle, traffic
    actually spread over replicas."""
    out = _run(setup=_ENGINES, body="""
    from repro.distributed import ReplicaGroup

    engine = tiny_engine("packed")
    devs = jax.devices()
    grp = ReplicaGroup(engine, devs, buckets=(1, 2), max_batch=2)
    grp.compile_buckets()
    before = grp.trace_count

    # each replica's executables hold params committed to ITS device
    pinned = set()
    for name, rep in grp.replicas.items():
        exe = rep.server._executable(2)
        arrs = jax.tree.leaves(exe.stage_executors[0].arrays)
        dev = {str(list(a.devices())[0]) for a in arrs}
        assert dev == {str(rep.devices[0])}, (name, dev)
        pinned |= dev
    assert len(pinned) == 4

    rng = np.random.default_rng(0)
    imgs = [rng.integers(0, 256, (16, 16, 3), dtype=np.uint8)
            for _ in range(12)]
    reqs = [grp.submit(i) for i in imgs]
    grp.drain()
    assert grp.trace_count == before
    ref = np.asarray(engine(jnp.asarray(np.stack(imgs))))
    for i, r in enumerate(reqs):
        assert r.outcome == "served"
        np.testing.assert_array_equal(np.asarray(r.result), ref[i])
    m = grp.metrics()
    served = {n: v["served"] for n, v in m["replicas"].items()}
    assert sum(served.values()) == 12
    assert sum(1 for v in served.values() if v) >= 2, served
    print("replica-parity-ok")
    """, n_dev=4)
    assert "replica-parity-ok" in out


def test_replicas_of_pipelines_forced_mesh():
    """Both axes composed: 2 replicas x 2-stage pipelines on 4 forced
    devices — the shape one sharded executable cannot express."""
    out = _run(setup=_ENGINES, body="""
    from repro.distributed import ReplicaGroup

    engine = tiny_engine("packed")
    grp = ReplicaGroup(engine, jax.devices(), devices_per_replica=2,
                       buckets=(2, 4), max_batch=4)
    assert set(grp.replicas) == {"r0", "r1"}
    grp.compile_buckets()
    rng = np.random.default_rng(0)
    imgs = [rng.integers(0, 256, (16, 16, 3), dtype=np.uint8)
            for _ in range(8)]
    reqs = [grp.submit(i) for i in imgs]
    grp.drain()
    ref = np.asarray(engine(jnp.asarray(np.stack(imgs))))
    for i, r in enumerate(reqs):
        np.testing.assert_array_equal(np.asarray(r.result), ref[i])
    for rep in grp.replicas.values():
        exe = rep.server._executable(4)
        if exe.plan.n_stages > 1:     # split realized: distinct devices
            assert len({str(d) for d in exe.devices}) == exe.plan.n_stages
    print("replica-pipeline-ok")
    """, n_dev=4)
    assert "replica-pipeline-ok" in out


def test_data_parallel_placement_matches_mesh_path():
    """DataParallel placement is exactly the mesh= path, through the
    unified placement surface."""
    out = _run(setup=_ENGINES, body="""
    from repro.distributed import DataParallel
    from repro.serving import InferenceServer

    engine = tiny_engine("packed")
    placement = DataParallel.over(4)
    assert placement.n_shards == 4
    sharded = InferenceServer(engine, buckets=(1, 2, 4, 8), max_batch=8,
                              placement=placement)
    assert sharded.scheduler.buckets == (4, 8)    # rounded to shard
    assert sharded.data_parallel == 4
    single = InferenceServer(engine, buckets=(4, 8), max_batch=8)
    sharded.compile_buckets(); single.compile_buckets()
    before = engine.trace_count
    rng = np.random.default_rng(0)
    imgs = [rng.integers(0, 256, (16, 16, 3), dtype=np.uint8)
            for _ in range(8)]
    rs = [sharded.submit(i) for i in imgs]
    ru = [single.submit(i) for i in imgs]
    sharded.drain(); single.drain()
    assert engine.trace_count == before
    for a, b in zip(rs, ru):
        np.testing.assert_array_equal(a.result, b.result)
    assert sharded.metrics()["placement"] == {"kind": "data", "shards": 4}
    print("dp-placement-ok")
    """, n_dev=4)
    assert "dp-placement-ok" in out


# --------------------------------------------------------------------------
# LM-stack multi-device scenarios (kept from the seed suite)
# --------------------------------------------------------------------------

_LM_PRELUDE = """
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import _mesh
mesh = _mesh((2, 4), ("data", "model"))
from repro.distributed.sharding import rules_for_mesh
rules = rules_for_mesh(mesh)
"""


def test_moe_sharded_matches_reference():
    _run(setup=_LM_PRELUDE, body="""
    from repro.models import moe as moe_lib

    t, d, e, k, fe = 64, 16, 8, 2, 32
    ks = jax.random.split(jax.random.key(0), 5)
    x = jax.random.normal(ks[0], (t, d), jnp.float32)
    router = jax.random.normal(ks[1], (d, e)) * 0.1
    wg = jax.random.normal(ks[2], (e, d, fe)) / np.sqrt(d)
    wu = jax.random.normal(ks[3], (e, d, fe)) / np.sqrt(d)
    wd = jax.random.normal(ks[4], (e, fe, d)) / np.sqrt(fe)

    with mesh:
        out, aux = jax.jit(lambda *a: moe_lib.moe_apply(
            *a, n_experts=e, top_k=k, capacity_factor=float(e),
            rules=rules, token_axes=("data", "model")))(
                x, router, wg, wu, wd)
    ref = moe_lib.moe_reference(x, router, wg, wu, wd, n_experts=e,
                                top_k=k)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=3e-2, atol=3e-2)
    # tokens replicated over model (decode path) must agree too
    with mesh:
        out2, _ = jax.jit(lambda *a: moe_lib.moe_apply(
            *a, n_experts=e, top_k=k, capacity_factor=float(e),
            rules=rules, token_axes=("data",)))(x, router, wg, wu, wd)
    np.testing.assert_allclose(np.asarray(out2, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=3e-2, atol=3e-2)
    print("moe OK")
    """)


def test_elastic_restore_different_mesh():
    _run(setup=_LM_PRELUDE, body="""
    import tempfile
    from repro.checkpoint import save, restore
    from repro.models import transformer
    from repro import configs
    from repro.launch.mesh import make_host_mesh

    cfg = configs.get("minitron-8b").smoke
    mesh_a = make_host_mesh(data=2, model=4)
    rules_a = rules_for_mesh(mesh_a)
    with mesh_a:
        psh_a = rules_a.tree_shardings(transformer.param_specs(cfg, rules_a))
        params = jax.jit(lambda k: transformer.init_params(k, cfg, ep=4),
                         out_shardings=psh_a)(jax.random.key(0))
    d = tempfile.mkdtemp()
    save(d, 3, params)

    # "node failure": restart on a smaller 4-device mesh
    mesh_b = make_host_mesh(data=4, model=1)
    rules_b = rules_for_mesh(mesh_b)
    with mesh_b:
        psh_b = rules_b.tree_shardings(transformer.param_specs(cfg, rules_b))
        like = jax.eval_shape(
            lambda k: transformer.init_params(k, cfg, ep=4),
            jax.random.key(0))
        restored = restore(d, 3, like, psh_b)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # restored arrays carry mesh_b shardings
    leaf = jax.tree.leaves(restored)[0]
    assert leaf.sharding.mesh.shape == {"data": 4, "model": 1}
    print("elastic OK")
    """)


def test_sharded_lm_matches_single_device():
    """The same smoke LM produces identical logits on (2,4) vs (1,1)."""
    _run(setup=_LM_PRELUDE, body="""
    from repro.models import transformer
    from repro import configs
    from repro.launch.mesh import make_host_mesh

    cfg = configs.get("qwen3-moe-30b-a3b").smoke
    import dataclasses
    cfg = dataclasses.replace(cfg, capacity_factor=8.0)  # no drops
    params = transformer.init_params(jax.random.key(0), cfg, ep=4)
    tokens = jax.random.randint(jax.random.key(1), (4, 32), 0, cfg.vocab)

    with mesh:  # (2, 4)
        lg_sharded, _ = jax.jit(
            lambda p, t: transformer.forward(p, t, cfg, rules))(params,
                                                                tokens)
    mesh1 = make_host_mesh(data=1, model=1)
    rules1 = rules_for_mesh(mesh1)
    # ep=4-padded weights work on a 1-device mesh too (padding is in E)
    with mesh1:
        lg_single, _ = jax.jit(
            lambda p, t: transformer.forward(p, t, cfg, rules1))(params,
                                                                 tokens)
    a = np.asarray(lg_sharded, np.float32)
    b = np.asarray(lg_single, np.float32)
    # bf16 end-to-end: partitioning changes accumulation order; a small
    # tail of logits drifts ~0.2 abs.  Assert tight agreement in bulk +
    # near-perfect argmax agreement (the decision-relevant quantity).
    np.testing.assert_allclose(a, b, rtol=0.1, atol=0.25)
    agree = (a.argmax(-1) == b.argmax(-1)).mean()
    # random-init logits have many near-ties; >95% argmax agreement is
    # the bf16-noise-tolerant bar
    assert agree > 0.95, agree
    print("sharded-vs-single OK", agree)
    """, timeout=560)
