"""Observability-layer tests (DESIGN.md §10).

The two contracts everything else hangs off:

* **disabled == free**: with no tracer installed, every instrumentation
  site is one global read returning a shared no-op — no allocation, no
  retrace, no measurable serve-path cost;
* **enabled == harmless**: spans are host-side only, so served results
  stay bit-exact and ``engine.trace_count`` stays flat while a traced
  burst flows.

Plus the canonical percentile math (pinned values — the one
implementation the servers, benchmarks, and summaries all share), the
registry primitives, the flight recorder ring, trace export/validation,
and benchmark provenance stamping.
"""

import itertools
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bnn_model
from repro.core.bnn_model import BConv, FloatDense, Pool
from repro.obs import flight, metrics, provenance, scopes, trace
from repro.serving import InferenceServer, PhoneBitEngine


@pytest.fixture(scope="module")
def tiny_engine():
    spec = [BConv(3, 32, kernel=3, stride=1, pad=1, first=True),
            Pool(2, 2), FloatDense(8 * 8 * 32, 10)]
    params = bnn_model.init_params(jax.random.key(0), spec)
    return PhoneBitEngine.from_trained(params, spec, (16, 16))


def _images(n, rng=None):
    rng = rng or np.random.default_rng(0)
    return [rng.integers(0, 256, (16, 16, 3), dtype=np.uint8)
            for _ in range(n)]


@pytest.fixture
def tracer():
    """Install a fresh tracer for one test; always uninstall after."""
    t = trace.install()
    yield t
    trace.uninstall()


# --------------------------------------------------------------------------
# Canonical percentile math
# --------------------------------------------------------------------------

class TestPercentile:
    def test_nearest_rank_pinned(self):
        vals = list(range(1, 21))                    # 1..20, sorted
        assert metrics.percentile(vals, 0.50) == 10
        assert metrics.percentile(vals, 0.95) == 19
        assert metrics.percentile(vals, 0.0) == 1
        assert metrics.percentile(vals, 1.0) == 20

    def test_empty_and_singleton(self):
        assert metrics.percentile([], 0.5) is None
        assert metrics.percentile([7.0], 0.5) == 7.0
        assert metrics.percentile([7.0], 0.95) == 7.0

    def test_summarize(self):
        s = metrics.summarize(range(1, 21))
        assert s == {"count": 20, "min": 1, "max": 20, "mean": 10.5,
                     "p50": 10, "p95": 19}
        assert metrics.summarize([])["p50"] is None

    def test_servers_use_canonical_math(self):
        """ServingMetrics percentiles == the canonical function (the
        dedupe satellite: no second latency-math implementation)."""
        sm = metrics.ServingMetrics(clock=lambda: 0.0)
        lats = [i / 1000 for i in range(1, 21)]
        sm.mark_dispatch()
        sm.record(lats)
        snap = sm.snapshot(dropped=0, queue_depth=0)
        assert snap["p50_ms"] == metrics.percentile(sorted(lats), .5) * 1e3
        assert snap["p95_ms"] == metrics.percentile(sorted(lats), .95) * 1e3


# --------------------------------------------------------------------------
# Registry primitives
# --------------------------------------------------------------------------

class TestRegistry:
    def test_counter_gauge_histogram(self):
        reg = metrics.MetricsRegistry()
        reg.counter("a").inc()
        reg.counter("a").inc(2)
        reg.gauge("g").set(42)
        reg.histogram("h").observe_many([1.0, 2.0, 3.0])
        snap = reg.snapshot()
        assert snap["a"] == 3 and snap["g"] == 42
        assert snap["h"]["count"] == 3 and snap["h"]["p50"] == 2.0

    def test_type_conflict_raises(self):
        reg = metrics.MetricsRegistry()
        reg.counter("x")
        with pytest.raises(TypeError, match="already registered"):
            reg.gauge("x")

    def test_events_bounded_and_filtered(self):
        reg = metrics.MetricsRegistry(max_events=3)
        for i in range(5):
            reg.event("tick", i=i)
        reg.event("other")
        assert len(reg.events()) == 3                # ring bounded
        assert [e["i"] for e in reg.events("tick")] == [3, 4]

    def test_use_registry_isolates(self):
        outer = metrics.get_registry()
        with metrics.use_registry() as reg:
            assert metrics.get_registry() is reg
            metrics.get_registry().counter("only.here").inc()
        assert metrics.get_registry() is outer
        assert "only.here" not in outer.snapshot()

    def test_reset(self):
        reg = metrics.MetricsRegistry()
        reg.counter("c").inc()
        reg.event("e")
        reg.reset()
        assert reg.snapshot() == {} and reg.events() == []


# --------------------------------------------------------------------------
# Flight recorder
# --------------------------------------------------------------------------

class TestFlightRecorder:
    def test_ring_bounds_and_order(self):
        fr = flight.FlightRecorder(capacity=3)
        for i in range(5):
            fr.record(i=i)
        assert len(fr) == 3
        assert [r["i"] for r in fr.dump()] == [2, 3, 4]  # oldest→newest
        assert fr.last(2)[-1]["i"] == 4

    def test_capacity_validation(self):
        with pytest.raises(ValueError):
            flight.FlightRecorder(capacity=0)

    def test_clear(self):
        fr = flight.FlightRecorder(capacity=4)
        fr.record(a=1)
        fr.clear()
        assert len(fr) == 0 and fr.dump() == []


# --------------------------------------------------------------------------
# Tracer + Chrome export
# --------------------------------------------------------------------------

class TestTracer:
    def test_disabled_returns_shared_null_span(self):
        assert trace.get_tracer() is None
        assert trace.span("anything", "serve", k=1) is trace.NULL_SPAN
        trace.instant("nothing")                     # no-op, no error
        with trace.span("scope") as s:
            assert s.set(x=1) is s                   # chainable no-op

    def test_spans_nest_and_export(self, tracer, tmp_path):
        with trace.span("outer", "test", a=1):
            with trace.span("inner", "test"):
                pass
        trace.instant("mark", "test", b=2)
        doc = tracer.export(tmp_path / "t.json")
        complete = trace.validate_trace(doc)
        assert [e["name"] for e in complete] == ["outer", "inner"]
        outer, inner = complete
        assert outer["ts"] <= inner["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] \
            + 1e-6
        on_disk = json.loads((tmp_path / "t.json").read_text())
        assert {e["name"] for e in on_disk["traceEvents"]} == \
            {"outer", "inner", "mark"}
        assert on_disk["metadata"]["schema"] == provenance.META_SCHEMA

    def test_span_set_attrs(self, tracer):
        with trace.span("s", "test") as sp:
            sp.set(shape=[1, 2])
        (ev,) = tracer.spans("s")
        assert ev["args"]["shape"] == [1, 2]

    def test_event_cap_counts_drops(self):
        t = trace.Tracer(max_events=2)
        for i in range(4):
            t.instant(f"e{i}")
        assert len(t.events) == 2 and t.dropped_events == 2

    def test_validate_rejects_partial_overlap(self):
        bad = [{"ph": "X", "name": "a", "ts": 0.0, "dur": 10.0,
                "pid": 0, "tid": 0},
               {"ph": "X", "name": "b", "ts": 5.0, "dur": 10.0,
                "pid": 0, "tid": 0}]
        with pytest.raises(ValueError, match="overlaps"):
            trace.validate_trace(bad)
        with pytest.raises(ValueError, match="name"):
            trace.validate_trace([{"ph": "X", "ts": 0, "dur": 1}])
        with pytest.raises(ValueError, match="dur"):
            trace.validate_trace([{"ph": "X", "name": "x", "ts": 0}])

    def test_uninstall_restores_fast_path(self):
        trace.install()
        try:
            assert trace.span("x") is not trace.NULL_SPAN
        finally:
            trace.uninstall()
        assert trace.span("x") is trace.NULL_SPAN


# --------------------------------------------------------------------------
# Provenance
# --------------------------------------------------------------------------

class TestProvenance:
    def test_meta_fields(self):
        m = provenance.provenance_meta()
        for k in ("schema", "git_sha", "jax", "jaxlib", "backend",
                  "device_kind", "n_devices", "backends", "timestamp"):
            assert k in m, k
        assert m["schema"] == provenance.META_SCHEMA
        assert m["jax"] == jax.__version__
        assert m["n_devices"] == len(jax.devices())
        assert "xla" in m["backends"]

    def test_write_bench_stamps(self, tmp_path):
        out = tmp_path / "BENCH_x.json"
        ret = provenance.write_bench(out, {"rows": [1, 2]})
        doc = json.loads(out.read_text())
        assert doc["rows"] == [1, 2]
        assert doc["meta"]["schema"] == provenance.META_SCHEMA
        assert ret["meta"] == doc["meta"]
        assert out.read_text().endswith("\n")


# --------------------------------------------------------------------------
# Serve-path integration: zero overhead off, harmless on
# --------------------------------------------------------------------------

class TestServeTracing:
    def test_disabled_serving_never_touches_tracer(self, tiny_engine):
        """Tracing off: the serve path sees NULL_SPAN only and the
        retrace contract holds exactly as before the obs layer."""
        assert trace.get_tracer() is None
        server = InferenceServer(tiny_engine, buckets=(1, 2, 4),
                                 max_batch=4)
        server.compile_buckets()
        before = tiny_engine.trace_count
        for img in _images(6):
            server.submit(img)
        server.drain()
        assert tiny_engine.trace_count == before
        assert server.metrics()["served"] == 6

    def test_traced_serving_bit_exact_and_no_retrace(self, tiny_engine,
                                                     tracer):
        """Tracing on: serve spans appear, results stay bit-exact vs the
        flat-path oracle, and trace_count stays flat — enabling
        observability is invisible to the compiled path."""
        server = InferenceServer(tiny_engine, buckets=(1, 2, 4),
                                 max_batch=4)
        server.compile_buckets()
        before = tiny_engine.trace_count
        imgs = _images(4)
        reqs = [server.submit(img) for img in imgs]
        server.drain()
        assert tiny_engine.trace_count == before     # flat under tracing
        ref = tiny_engine.cross_check(np.stack(imgs))
        for r, row in zip(reqs, np.asarray(ref)):
            np.testing.assert_array_equal(np.asarray(r.result), row)
        names = {e["name"] for e in tracer.events}
        assert {"serve.submit", "serve.assemble", "serve.stage",
                "serve.dispatch", "serve.device",
                "serve.scatter"} <= names
        trace.validate_trace(tracer.events)

    def test_flight_recorder_sees_served_and_shed(self, tiny_engine):
        t = {"now": 0.0}
        server = InferenceServer(tiny_engine, buckets=(1, 2),
                                 max_batch=2, clock=lambda: t["now"])
        server.compile_buckets()
        server.submit(_images(1)[0], deadline_s=1.0)   # will expire
        ok = server.submit(_images(1)[0])
        t["now"] = 2.0
        server.drain()
        assert ok.done
        outcomes = [r["outcome"] for r in server.flight.dump()]
        assert sorted(outcomes) == ["served", "shed"]
        shed = next(r for r in server.flight.dump()
                    if r["outcome"] == "shed")
        assert shed["deadline_s"] == 1.0 and shed["done_s"] == 2.0
        served = next(r for r in server.flight.dump()
                      if r["outcome"] == "served")
        assert served["latency_s"] == pytest.approx(2.0)
        assert served["queue_s"] <= served["latency_s"]

    def test_enabled_overhead_under_two_percent(self, tiny_engine,
                                                tracer):
        """The <2% budget (ISSUE acceptance): measured per-span cost ×
        spans-per-request must sit well inside the measured p50 request
        latency.  Span cost is a min-over-reps estimate (noise only ever
        adds time)."""
        server = InferenceServer(tiny_engine, buckets=(1, 2, 4),
                                 max_batch=4)
        server.compile_buckets()
        n_before = len(tracer.events)
        reqs = _images(8)
        for img in reqs:
            server.submit(img)
        server.drain()
        p50_s = server.metrics()["p50_ms"] / 1e3
        spans_per_req = (len(tracer.events) - n_before) / len(reqs)
        cost = min(_timed_spans(100) for _ in range(5))
        assert cost * spans_per_req < 0.02 * p50_s, (
            f"span cost {cost * 1e6:.2f}us x {spans_per_req:.1f} "
            f"spans/req vs p50 {p50_s * 1e3:.2f}ms")


def _timed_spans(n):
    """Mean seconds per open/close span cycle over ``n`` spans."""
    import time
    t0 = time.perf_counter()
    for _ in range(n):
        with trace.span("overhead.probe", "test"):
            pass
    return (time.perf_counter() - t0) / n


# --------------------------------------------------------------------------
# Node scopes on the served executable
# --------------------------------------------------------------------------

def _scope_set(op_maps):
    return {sc for mp in op_maps.values() for sc in mp.values()}


def _chain_engine(mode="vpu_chain"):
    # The first conv runs on the MXU outside every chain; conv2+pool and
    # conv3 form the region.
    spec = [BConv(3, 16, kernel=3, stride=1, pad=1, first=True),
            BConv(16, 16, kernel=3, stride=1, pad=1),
            Pool(2, 2), BConv(16, 16, kernel=3, stride=1, pad=1),
            FloatDense(8 * 8 * 16, 4)]
    params = bnn_model.init_params(jax.random.key(1), spec)
    return PhoneBitEngine.from_trained(params, spec, (16, 16),
                                       matmul_mode=mode)


class TestNodeScopes:
    @pytest.mark.parametrize("op_name,scope", [
        ("jit(_run)/n3.packed_conv_pool/jit(chain_conv)/direct_conv_pool/"
         "pallas_call", "n3.packed_conv_pool"),
        ("jit(_run)/region.3+5/jit(chain_conv)/jit(_pad)/pad", "region.3+5"),
        ("jit(traced_head)/head/jit(nms)/while", "head"),
        ("jit(_run)/shard_map/n12.unpack_pm1/shift_right_logical",
         "n12.unpack_pm1"),
        ("x", "none"),
        ("", "none"),
    ])
    def test_scope_of(self, op_name, scope):
        assert scopes.scope_of(op_name) == scope

    def test_argument_copies_charged_to_their_reader(self):
        """XLA's layout copies of an argument carry its path (quotes
        escaped in the HLO text), not a scope: the map charges them to
        the node that reads the argument."""
        text = "\n".join([
            "HloModule jit__run, entry_computation_layout={}",
            "  %copy.6 = s32[3] copy(s32[3] %p), metadata={op_name="
            "\"arrays[\\'3\\'][\\'w_packed\\']\"}",
            "  %copy.7 = u8[3] copy(u8[3] %x.1), metadata={op_name=\"x\"}",
            "  %copy-start = (s32[3]) copy-start(s32[3] %q)",
            "  ROOT %chain_region.3 = s32[3] custom-call(s32[3] %copy.6), "
            "metadata={op_name=\"jit(_run)/region.3+5/chain_region/"
            "pallas_call\" stack_frame_id=2}"])
        got = scopes.op_scopes(text, {"arrays['3']": "region.3+5",
                                      "x": "n1.bitplane_expand"})
        assert got == {"jit__run": {"copy.6": "region.3+5",
                                    "copy.7": "n1.bitplane_expand",
                                    "copy-start": "none",
                                    "chain_region.3": "region.3+5"}}

    def test_every_node_scoped_bit_exact_no_retrace(self, tiny_engine):
        """The served executable's ops carry their node's scope; building
        the map retraces and recompiles nothing and changes no bit."""
        exe = tiny_engine.compile(2)
        x = jnp.asarray(np.stack(_images(2)))
        ref = np.asarray(exe(x))
        before = tiny_engine.trace_count
        maps = exe.op_scopes(x)
        assert exe.op_scopes(x) is maps                  # built once
        assert tiny_engine.trace_count == before
        (module,) = maps
        assert module.startswith("jit_")
        want = {f"n{nid}.{n.op}" for nid, n in exe.graph.nodes.items()
                if n.op != "input"}
        assert want <= _scope_set(maps)
        np.testing.assert_array_equal(np.asarray(exe(x)), ref)
        assert tiny_engine.trace_count == before

    def test_chain_regions_scoped(self):
        """A vpu_chain executor's fused regions are scopes of their own
        (member nodes never appear alone), bit-exact with per-node
        serving."""
        eng = _chain_engine()
        exe = eng.compile(1)
        x = jnp.asarray(np.stack(_images(1)))
        got = np.asarray(exe(x))
        before = eng.trace_count
        found = _scope_set(exe.op_scopes(x))
        assert eng.trace_count == before
        assert exe.regions
        for chain in exe.regions:
            assert "region." + "+".join(map(str, chain.node_ids)) in found
            assert not any(sc.startswith(f"n{nid}.") for sc in found
                           for nid in chain.node_ids)
        # The MXU first conv keeps its node scope; no expansion runs.
        assert "n2.packed_conv" in found
        assert not any(sc.endswith(".bitplane_expand") for sc in found)
        ref = _chain_engine("xla").compile(1)(x)
        np.testing.assert_array_equal(got, np.asarray(ref))

    def test_tracing_on_changes_no_bit(self, tiny_engine):
        exe = tiny_engine.compile(1)
        x = jnp.asarray(np.stack(_images(1)))
        off = np.asarray(exe(x))
        before = tiny_engine.trace_count
        trace.install(trace.Tracer(annotate_jax=True))
        try:
            on = np.asarray(exe(x))
        finally:
            trace.uninstall()
        np.testing.assert_array_equal(on, off)
        assert tiny_engine.trace_count == before

    def test_server_maps_forward_and_head(self):
        """Per bucket, the server maps both modules it dispatches: the
        forward's nodes and the postprocess head, after compile_buckets
        and with no retrace."""
        from repro import workloads

        wl = workloads.get("yolov2_tiny_voc", variant="tiny", seed=3)
        server = wl.server(buckets=(1, 2), max_batch=2, preprocess=None)
        server.compile_buckets()
        before = wl.engine.trace_count
        maps = server.op_scopes(2)
        assert len(maps) == 2
        fwd, head = (maps[m] for m in sorted(maps, key=lambda m: "head" in m))
        assert "head" in set(head.values()) <= {"head", "none"}
        assert "n1.bitplane_expand" in set(fwd.values())
        server.submit(np.zeros((32, 32, 3), np.uint8))
        server.drain()
        assert wl.engine.trace_count == before

    def test_fused_call_whole_span_when_enabled(self, tiny_engine,
                                                tracer):
        exe = tiny_engine.compile(1)
        exe(np.stack(_images(1)))
        (ev,) = tracer.spans("executor.call")
        assert ev["args"]["nodes"] > 0


# --------------------------------------------------------------------------
# Request stage stamps in the flight recorder
# --------------------------------------------------------------------------

def _ticking():
    """A fake clock that moves 1 ms on every read."""
    ticks = itertools.count()
    return lambda: next(ticks) * 1e-3


class TestFlightStages:
    @pytest.mark.parametrize("hook", [False, True])
    def test_stamps_ordered(self, tiny_engine, hook):
        server = InferenceServer(
            tiny_engine, buckets=(1, 2), max_batch=2, clock=_ticking(),
            preprocess=(lambda p: p) if hook else None)
        for img in _images(3):
            server.submit(img)
        server.drain()
        recs = [r for r in server.flight.dump() if r["outcome"] == "served"]
        assert len(recs) == 3
        for r in recs:
            assert (r["arrival_s"] <= r["assembled_s"] < r["dispatched_s"]
                    < r["ready_s"] < r["done_s"])
            assert r["queue_s"] == r["dispatched_s"] - r["arrival_s"]
            assert r["stage_s"] > 0
            if hook:
                assert 0 < r["preprocess_s"] <= r["stage_s"]
            else:
                assert "preprocess_s" not in r

    def test_preprocess_spans_when_tracing(self, tiny_engine, tracer):
        server = InferenceServer(tiny_engine, buckets=(2,), max_batch=2,
                                 preprocess=lambda p: p)
        reqs = [server.submit(img) for img in _images(2)]
        server.drain()
        pre = tracer.spans("serve.preprocess")
        assert sorted(e["args"]["req"] for e in pre) == \
            sorted(r.id for r in reqs)
        (stage,) = tracer.spans("serve.stage")
        assert all(stage["ts"] <= e["ts"] and e["ts"] + e["dur"]
                   <= stage["ts"] + stage["dur"] for e in pre)

    def test_one_program_clock(self, tiny_engine):
        """Flight stamps, spans and the servers' default clocks read one
        function: the benchmark's clock."""
        import time

        assert trace.clock is time.perf_counter
        assert trace.Tracer().clock is trace.clock
        assert InferenceServer(tiny_engine).clock is trace.clock
        assert metrics.ServingMetrics()._clock is trace.clock

    def test_clock_anchor_written_once(self):
        """An annotating tracer writes one ``obs.clock`` annotation, at
        its first span, and keeps the clock reading taken inside it."""
        reads = itertools.count(5.0)
        t = trace.Tracer(clock=lambda: next(reads), annotate_jax=True)
        assert t.anchor_s is None                   # 5.0: the epoch
        with t.span("serve.a"):                     # 6.0: the anchor
            pass
        with t.span("serve.b"):
            pass
        assert t.anchor_s == 6.0
        assert [e["name"] for e in t.events] == ["serve.a", "serve.b"]


# --------------------------------------------------------------------------
# Runtime-wide metrics series
# --------------------------------------------------------------------------

class TestRuntimeSeries:
    def test_retrace_counter_and_arena_gauge(self):
        spec = [BConv(3, 16, kernel=3, stride=1, pad=1, first=True),
                Pool(2, 2), FloatDense(8 * 8 * 16, 4)]
        params = bnn_model.init_params(jax.random.key(2), spec)
        with metrics.use_registry() as reg:
            eng = PhoneBitEngine.from_trained(params, spec, (16, 16))
            x = np.stack(_images(2))
            jax.block_until_ready(eng(x))
            jax.block_until_ready(eng(x))            # cached: no retrace
            assert reg.counter("runtime.retraces").value == 1
            assert reg.gauge("runtime.arena_peak_bytes").value > 0

    def test_chain_vmem_gauge(self):
        """A vpu_chain bucket publishes its largest region's VMEM plan at
        the tile it serves; a bucket without regions leaves it unset."""
        with metrics.use_registry() as reg:
            _chain_engine("xla").compile(1)
            assert reg.gauge("runtime.chain_vmem_bytes").value is None
            exe = _chain_engine().compile(1)
            assert exe.regions
            assert reg.gauge("runtime.chain_vmem_bytes").value == max(
                c.tiling().vmem.total_bytes() for c in exe.regions) > 0

    def test_autotune_events(self, tmp_path, monkeypatch):
        """The structured autotune audit trail: fresh sweeps emit miss
        events with a sweep size, a second engine over the same graph
        hits in memory."""
        from repro.runtime.autotune import Autotuner

        monkeypatch.setenv("REPRO_AUTOTUNE_CACHE",
                           str(tmp_path / "cache.json"))
        # conv2 is timed; the first conv takes the MXU lowering untimed.
        spec = [BConv(3, 16, kernel=3, stride=1, pad=1, first=True),
                Pool(2, 2), BConv(16, 16, kernel=3, stride=1, pad=1),
                FloatDense(8 * 8 * 16, 4)]
        params = bnn_model.init_params(jax.random.key(3), spec)
        eng = PhoneBitEngine.from_trained(params, spec, (16, 16),
                                          matmul_mode="auto")
        with metrics.use_registry() as reg:
            t1 = Autotuner(warmup=0, iters=1)
            t1.tune(eng._graph, eng._plan_shape(1))
            misses = reg.events("autotune")
            assert misses and all(e["outcome"] == "miss" for e in misses)
            assert all(e["sweep_size"] >= 1 for e in misses)
            assert reg.counter("autotune.miss").value == len(misses)
            # same tuner, same graph → pure in-memory hits
            t1.tune(eng._graph, eng._plan_shape(1))
            assert reg.counter("autotune.hit").value == len(misses)
            # new tuner, same disk cache → disk warm-start
            t2 = Autotuner(warmup=0, iters=1)
            t2.tune(eng._graph, eng._plan_shape(1))
            assert reg.counter("autotune.disk_hit").value == len(misses)


# --------------------------------------------------------------------------
# LM / BNN metrics parity
# --------------------------------------------------------------------------

def test_lm_metrics_parity_with_inference_server(tiny_engine):
    """Both servers emit the same core metrics vocabulary with the same
    semantics (the §7 protocol contract, now enforced through the one
    shared ServingMetrics)."""
    from repro.distributed.sharding import rules_for_mesh
    from repro.launch.mesh import make_host_mesh
    from repro.models import transformer
    from repro.serving.lm_server import LMServer

    server = InferenceServer(tiny_engine, buckets=(1, 2), max_batch=2)
    for img in _images(3):
        server.submit(img)
    server.drain()
    bnn_m = server.metrics()

    cfg = transformer.LMConfig(
        name="parity-demo", n_layers=1, d_model=64, n_heads=2,
        n_kv_heads=1, d_head=32, d_ff=128, vocab=128,
        tie_embeddings=True)
    mesh = make_host_mesh(data=1, model=1)
    with mesh:
        params = transformer.init_params(jax.random.key(0), cfg, ep=1)
        lm = LMServer(cfg=cfg, rules=rules_for_mesh(mesh), params=params,
                      n_slots=2, max_seq=32)
        rng = np.random.default_rng(0)
        for _ in range(3):
            lm.submit(list(rng.integers(1, cfg.vocab, 4)), max_new=2)
        lm.drain()
        lm_m = lm.metrics()

    core = {"served", "dropped", "queue_depth", "p50_ms", "p95_ms",
            "throughput"}
    assert core <= set(bnn_m) and core <= set(lm_m)
    for m in (bnn_m, lm_m):
        assert m["served"] == 3 and m["dropped"] == 0
        assert m["queue_depth"] == 0
        assert m["p50_ms"] is not None and m["p50_ms"] <= m["p95_ms"]
        assert m["throughput"] is None or m["throughput"] > 0
    # the registries behind both expose the same series names
    assert set(server.metrics_registry.snapshot()) == \
        set(lm.metrics_registry.snapshot())
