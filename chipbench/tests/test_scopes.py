"""Device time per scope, the program clock on the trace, and the
flight-recorder readers: on the recorded v5e trace, on a profiler trace
taken here on the CPU, and on hand-made records whose answers are known."""

import gzip
import shutil
import time

import pytest

from chipbench import harness, scopes, trace_reduce
from chipbench.tests.test_trace_reduce import RECORDED


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp("xplane") / "t.xplane.pb"
    with gzip.open(RECORDED, "rb") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return str(path)


def test_scope_sums_equal_device_op_sums(recorded):
    """Through a map of three forward ops, each scope holds exactly the
    device_ops seconds of its ops, the head's ops stay out of it (another
    module), and nothing is lost: the scopes sum to every op's time."""
    st = scopes.load(recorded)
    ops = dict(trace_reduce.reduce(trace_reduce.load(recorded),
                                   n_top=10**6)["device_ops"])
    assert {m for m, *_ in st.ops} == {"jit__run", "jit_traced_head"}
    fwd = {"chain_conv.5": "n3.packed_conv_pool",
           "pad.17": "n3.packed_conv_pool",
           "copy.13": "n1.bitplane_expand"}
    head_only = {n for m, n, *_ in st.ops if m == "jit_traced_head"} \
        - {n for m, n, *_ in st.ops if m == "jit__run"}
    name = sorted(head_only)[0]
    got = scopes.scope_seconds(st, {"jit__run": fwd,
                                    "jit_traced_head": {name: "head"}})
    assert got["n3.packed_conv_pool"] == pytest.approx(
        ops["%chain_conv.5"] + ops["%pad.17"])
    assert got["n1.bitplane_expand"] == pytest.approx(ops["%copy.13"])
    assert got["head"] == pytest.approx(ops["%" + name])
    assert sum(got.values()) == pytest.approx(sum(ops.values()))
    assert scopes.scope_seconds(st, {"jit_traced_head": fwd}) == \
        {"none": pytest.approx(sum(ops.values()))}


@pytest.mark.parametrize("found,first", [
    ({"n1.bitplane_expand", "n3.packed_conv_pool", "n5.packed_conv_pool",
      "n10.packed_dense", "head", "none"},
     ["n1.bitplane_expand", "n3.packed_conv_pool"]),
    ({"n16.unpack_pm1", "region.7+9+11+13", "region.3+5",
      "n1.bitplane_expand"}, ["n1.bitplane_expand", "region.3+5"]),
    ({"n1.bitplane_expand", "n12.float_dense", "none"}, []),
])
def test_first_conv_scopes(found, first):
    assert scopes.first_conv_scopes(found) == first


def test_anchor_maps_a_stamp_onto_its_annotation(tmp_path):
    """A program clock reading taken inside a span lands, through the
    ``obs.clock`` anchor, within 100 us of the span's own annotation on a
    profiler trace (CPU here; the same clocks on the chip's host)."""
    import jax

    from repro.obs import trace as obs_trace

    tracer = obs_trace.install(obs_trace.Tracer(annotate_jax=True))
    jax.profiler.start_trace(str(tmp_path))
    stamps = []
    try:
        for k in range(3):
            time.sleep(0.02)
            with obs_trace.span(f"serve.probe{k}"):
                stamps.append(obs_trace.clock())
    finally:
        jax.profiler.stop_trace()
        obs_trace.uninstall()
    st = scopes.load(trace_reduce.find_xplane(str(tmp_path)))
    at = scopes.anchor_ns(st)
    assert at is not None and tracer.anchor_s is not None
    starts = {n: s for n, s, _ in st.host if n.startswith("serve.probe")}
    for k, t in enumerate(stamps):
        mapped = scopes.to_trace_ns(t, tracer.anchor_s, at)
        assert abs(mapped - starts[f"serve.probe{k}"]) < 100e3


def test_batch_device_ms_reads_busy_time_between_stamps():
    ms = 1_000_000
    st = scopes.ScopedTrace(
        ops=[("m", "a", 10 * ms, 14 * ms), ("m", "b", 12 * ms, 16 * ms),
             ("m", "c", 30 * ms, 31 * ms), ("m", "d", 50 * ms, 60 * ms)],
        host=[("obs.clock", 0, 2 * ms)])
    flight = [  # program clock: anchor at 100.0 s == 1 ms on the trace
        dict(outcome="served", dispatched_s=100.008, ready_s=100.0305),
        dict(outcome="served", dispatched_s=100.008, ready_s=100.0305),
        dict(outcome="served", dispatched_s=100.044, ready_s=100.054),
        dict(outcome="shed")]
    got = scopes.batch_device_ms(st, flight, 100.0, scopes.anchor_ns(st))
    assert got == [pytest.approx(7.0), pytest.approx(5.0)]
    assert scopes.anchor_ns(scopes.ScopedTrace([], [])) is None


def _run(flight):
    return harness.Run("c", 1.0, 1, 0.0, 0.0, 1.0, [], flight, [], None)


def _served(**kw):
    return {**dict(outcome="served", arrival_s=1.0, dispatched_s=1.006,
                   stage_s=0.005, bucket=1), **kw}


def test_flight_readers():
    flight = [_served(assembled_s=1.001, ready_s=1.012, preprocess_s=0.004),
              _served(assembled_s=1.003, ready_s=1.012, preprocess_s=0.002),
              _served(assembled_s=1.002, ready_s=1.016, preprocess_s=0.003,
                      dispatched_s=1.010),
              dict(outcome="shed", arrival_s=1.0)]
    run = _run(flight)
    assert harness.reader("preprocess_ms_p50")(run) == pytest.approx(3.0)
    assert harness.reader("sched_wait_ms_p95")(run) == pytest.approx(3.0)
    # two batches, by (dispatched_s, ready_s): 6 ms and 6 ms
    assert harness.reader("device_ms_p50")(run) == pytest.approx(6.0)


@pytest.mark.parametrize("flight", [
    [],                                    # nothing served
    [_served(), _served()],                # a program without the stamps
])
def test_flight_readers_find_nothing(flight):
    for name in ("preprocess_ms_p50", "sched_wait_ms_p95", "device_ms_p50"):
        assert harness.reader(name)(_run(flight)) is None
