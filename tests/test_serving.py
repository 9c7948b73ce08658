"""Serving-subsystem tests (DESIGN.md §7).

Scheduler policy under an injected fake clock (max_wait firing, bucket
rounding, zero-padding, deadline shedding, the run-only-at-bucket-sizes
contract), the InferenceServer (per-bucket executable cache → zero
serve-time retraces, async == sync results, bit-exactness vs the engine
cross-check oracle, metrics), cross-bucket autotune reuse, data-parallel
batch sharding (in a subprocess with placeholder devices), and the LM
server speaking the same protocol.
"""

import os
import pathlib
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bnn_model
from repro.core.bnn_model import BConv, FloatDense, Pool
from repro.serving import (BatchScheduler, InferenceServer, PhoneBitEngine,
                           Server)

REPO = pathlib.Path(__file__).resolve().parent.parent


# --------------------------------------------------------------------------
# Scheduler policy (fake clock)
# --------------------------------------------------------------------------

class TestSchedulerPolicy:
    def test_max_wait_fires(self):
        s = BatchScheduler(max_batch=4, max_wait_s=0.005)
        s.submit("a", now=100.0)
        assert s.next_batch(now=100.004) is None      # still waiting
        batch = s.next_batch(now=100.006)             # max_wait passed
        assert [r.payload for r in batch] == ["a"]

    def test_full_batch_fires_immediately(self):
        s = BatchScheduler(max_batch=2, max_wait_s=10.0, buckets=(1, 2))
        s.submit("a", now=0.0)
        s.submit("b", now=0.0)
        assert len(s.next_batch(now=0.0)) == 2

    def test_bucket_rounding(self):
        s = BatchScheduler(max_batch=8, max_wait_s=0.0, buckets=(1, 4, 8))
        assert [s.bucket_for(n) for n in (1, 2, 3, 4, 5, 8)] == \
            [1, 4, 4, 4, 8, 8]

    def test_drain_zero_pads_and_slices(self):
        s = BatchScheduler(max_batch=8, max_wait_s=0.0, buckets=(1, 4, 8))
        for i in range(3):
            s.submit(np.full((2, 2), i + 1, np.int32), now=0.0)
        seen = {}

        def run(payloads):
            seen["n"] = len(payloads)
            seen["pad"] = payloads[3]
            return [p * 10 for p in payloads]

        done = s.drain(run, now=0.0)
        assert seen["n"] == 4                        # padded 3 -> bucket 4
        np.testing.assert_array_equal(seen["pad"],
                                      np.zeros((2, 2), np.int32))
        assert len(done) == 3                        # pad row discarded
        np.testing.assert_array_equal(done[0].result,
                                      np.full((2, 2), 10, np.int32))

    def test_deadline_shedding(self):
        s = BatchScheduler(max_batch=4, max_wait_s=0.0, buckets=(1, 2, 4))
        patient = s.submit("p", now=0.0)                  # no deadline
        hasty = s.submit("h", deadline_s=1.0, now=0.0)    # expires at 1.0
        shed = s.shed_expired(now=0.5)
        assert shed == [] and len(s) == 2
        batch = s.next_batch(now=2.0)                     # hasty expired
        assert [r.payload for r in batch] == ["p"]
        assert hasty.done and hasty.result is None
        assert s.dropped == 1 and not patient.done

    def test_expired_mid_queue_is_shed(self):
        s = BatchScheduler(max_batch=8, max_wait_s=0.0, buckets=(1, 2, 4, 8))
        s.submit("a", now=0.0)
        doomed = s.submit("b", deadline_s=0.5, now=0.0)
        s.submit("c", now=0.0)
        batch = s.next_batch(now=1.0)
        assert [r.payload for r in batch] == ["a", "c"]
        assert doomed.done and s.dropped == 1

    def test_deadline_shedding_wall_clock(self):
        """Smoke the shed policy against the REAL monotonic clock (every
        other policy test injects a fake one, so a regression in the
        default clock path could hide).  Margins are generous — the
        doomed deadline (50 ms) is 5x shorter than the sleep (250 ms),
        and the patient deadline (60 s) is ~240x longer — so scheduler
        slowness cannot flip the outcome."""
        import time as _time

        s = BatchScheduler(max_batch=4, max_wait_s=0.0, buckets=(1, 2, 4))
        patient = s.submit("p", deadline_s=60.0)
        doomed = s.submit("d", deadline_s=0.05)
        _time.sleep(0.25)
        batch = s.next_batch()                     # no now=: real clock
        assert [r.payload for r in batch] == ["p"]
        assert doomed.done and doomed.result is None
        assert s.dropped == 1 and not patient.result

    def test_max_wait_wall_clock(self):
        """ready() flips from False to True by real elapsed time."""
        import time as _time

        s = BatchScheduler(max_batch=8, max_wait_s=0.1, buckets=(1, 8))
        s.submit("a")
        assert not s.ready()          # 100 ms cannot have elapsed yet
        _time.sleep(0.3)
        assert s.ready()

    def test_drain_only_calls_run_at_bucket_sizes(self):
        buckets = (1, 2, 4, 8)
        s = BatchScheduler(max_batch=8, max_wait_s=0.0, buckets=buckets)
        sizes = []

        def run(payloads):
            sizes.append(len(payloads))
            return payloads

        for n in (1, 3, 5, 8, 2, 7, 6, 4):
            for i in range(n):
                s.submit(i, now=0.0)
            while len(s):
                s.drain(run, now=0.0)
        assert sizes and all(n in buckets for n in sizes)


# --------------------------------------------------------------------------
# InferenceServer over a tiny BNN engine
# --------------------------------------------------------------------------

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


class TestInferenceServer:
    def test_protocol(self, tiny_engine):
        server = InferenceServer(tiny_engine, buckets=(1, 2), max_batch=2)
        assert isinstance(server, Server)

    def test_zero_recompiles_after_bucket_precompile(self, tiny_engine):
        server = InferenceServer(tiny_engine, buckets=(1, 2, 4),
                                 max_batch=4, max_wait_s=0.0)
        server.compile_buckets()
        before = tiny_engine.trace_count
        # mixed-size stream: singles, pairs, odd group padded to 4
        for group in (1, 2, 3, 4, 1, 3):
            for img in _images(group):
                server.submit(img)
            server.drain()
        assert tiny_engine.trace_count == before     # the serve contract
        assert server.metrics()["served"] == 14

    def test_results_bit_exact_vs_cross_check(self, tiny_engine):
        server = InferenceServer(tiny_engine, buckets=(1, 2, 4),
                                 max_batch=4, max_wait_s=0.0)
        server.compile_buckets()
        for group in (4, 2, 1):           # full buckets: no padding rows
            imgs = _images(group, np.random.default_rng(group))
            reqs = [server.submit(i) for i in imgs]
            server.drain()
            ref = tiny_engine.cross_check(jnp.asarray(np.stack(imgs)))
            for i, r in enumerate(reqs):
                np.testing.assert_array_equal(r.result,
                                              np.asarray(ref)[i])

    def test_padded_results_match_unpadded_rows(self, tiny_engine):
        """A request served in a padded bucket gets the same row it would
        in the explicitly padded batch (pad rows are zeros)."""
        server = InferenceServer(tiny_engine, buckets=(1, 2, 4),
                                 max_batch=4, max_wait_s=0.0)
        imgs = _images(3, np.random.default_rng(7))
        reqs = [server.submit(i) for i in imgs]
        server.drain()
        padded = np.stack(imgs + [np.zeros_like(imgs[0])])
        ref = np.asarray(tiny_engine(jnp.asarray(padded)))
        for i, r in enumerate(reqs):
            np.testing.assert_array_equal(r.result, ref[i])

    def test_async_matches_sync(self, tiny_engine):
        outs = {}
        for mode in (True, False):
            server = InferenceServer(tiny_engine, buckets=(1, 2, 4),
                                     max_batch=4, async_dispatch=mode)
            reqs = [server.submit(i)
                    for i in _images(9, np.random.default_rng(3))]
            done = server.drain()
            assert len(done) == 9 and all(r.done for r in reqs)
            outs[mode] = [r.result for r in reqs]
        for a, b in zip(outs[True], outs[False]):
            np.testing.assert_array_equal(a, b)

    def test_deadline_shed_through_server(self, tiny_engine):
        t = {"now": 0.0}
        server = InferenceServer(tiny_engine, buckets=(1, 2), max_batch=2,
                                 clock=lambda: t["now"])
        kept = server.submit(_images(1)[0], now=0.0)
        shed = server.submit(_images(1)[0], deadline_s=1.0, now=0.0)
        t["now"] = 5.0
        server.drain(now=5.0)
        assert kept.done and kept.result is not None
        assert shed.done and shed.result is None
        m = server.metrics()
        assert m["dropped"] == 1 and m["served"] == 1

    def test_metrics_shape(self, tiny_engine):
        server = InferenceServer(tiny_engine, buckets=(1, 2, 4),
                                 max_batch=4)
        for img in _images(6):
            server.submit(img)
        server.drain()
        m = server.metrics()
        assert m["served"] == 6 and m["dropped"] == 0
        assert m["queue_depth"] == 0
        assert 0 < m["p50_ms"] <= m["p95_ms"]
        assert m["throughput"] > 0
        assert m["async_dispatch"] is True

    def test_compile_rejects_unshardable_bucket(self, tiny_engine):
        with pytest.raises(ValueError, match="divisible"):
            tiny_engine.compile(5, data_parallel=2)

    def test_preprocess_hook(self, tiny_engine):
        """Payloads arrive at 32x32 and the preprocess hook (2x2 mean
        pool to the engine's 16x16) runs at batch staging — identically
        under sync and async dispatch, pads included."""
        def pool2(img):
            x = img.astype(np.uint16)
            x = (x[0::2, 0::2] + x[1::2, 0::2]
                 + x[0::2, 1::2] + x[1::2, 1::2]) // 4
            return x.astype(np.uint8)

        rng = np.random.default_rng(11)
        raw = [rng.integers(0, 256, (32, 32, 3), dtype=np.uint8)
               for _ in range(3)]
        outs = {}
        for mode in (True, False):
            server = InferenceServer(tiny_engine, buckets=(1, 2, 4),
                                     max_batch=4, async_dispatch=mode,
                                     preprocess=pool2)
            reqs = [server.submit(r) for r in raw]
            server.drain()        # 3 requests -> bucket 4, zero pad
            outs[mode] = [r.result for r in reqs]
        ref = np.asarray(tiny_engine(jnp.asarray(np.stack(
            [pool2(r) for r in raw] + [np.zeros((16, 16, 3), np.uint8)]))))
        for mode in (True, False):
            for i, got in enumerate(outs[mode]):
                np.testing.assert_array_equal(got, ref[i])


# --------------------------------------------------------------------------
# Cross-bucket autotune reuse
# --------------------------------------------------------------------------

class TestCrossBucketAutotune:
    def test_second_bucket_reuses_first(self, monkeypatch):
        from repro import runtime
        from repro.runtime.autotune import Autotuner

        # conv2 is timed; the first conv takes the MXU lowering untimed.
        spec = [BConv(3, 32, kernel=3, stride=1, pad=1, first=True),
                Pool(2, 2), BConv(32, 32, kernel=3, stride=1, pad=1),
                FloatDense(8 * 8 * 32, 10)]
        params = bnn_model.init_params(jax.random.key(0), spec)
        from repro.core import converter
        packed = converter.convert(params, spec, (16, 16))
        g = runtime.fuse_pool_epilogue(
            runtime.lower_packed(spec, packed, (16, 16)))

        timed = []
        orig = Autotuner._time_node

        def counting(self, node, x, backend, tile):
            timed.append(x.shape)
            return orig(self, node, x, backend, tile)

        monkeypatch.setattr(Autotuner, "_time_node", counting)
        monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", "0")
        tuner = Autotuner(candidates=("xla", "xla_pm1"), warmup=0, iters=1)
        c1, _ = tuner.tune_with_tiles(g, (1, 16, 16, 3))
        n_first = len(timed)
        assert n_first > 0
        c4, _ = tuner.tune_with_tiles(g, (4, 16, 16, 3))
        assert len(timed) == n_first        # batch 4: zero new timings
        assert c4 == c1                     # same winners, transferred
        reused = [e for e in tuner.cache.values()
                  if e.get("reused_across_batch")]
        assert reused

    def test_block_n_tile_does_not_transfer(self):
        from repro.runtime.autotune import Autotuner

        tuner = Autotuner(candidates=("xla",), persist=False)
        tuner.agnostic_cache["batchless::k"] = {
            "winner": "vpu_direct", "tile": {"block_n": 4}}
        assert tuner._cross_batch_entry("batchless::k") is None
        tuner.agnostic_cache["batchless::k2"] = {
            "winner": "vpu_direct", "tile": {"block_h": 8}}
        assert tuner._cross_batch_entry("batchless::k2") is not None


# --------------------------------------------------------------------------
# Data-parallel batch sharding (subprocess: placeholder devices)
# --------------------------------------------------------------------------

def test_sharded_serving_matches_single_device():
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        os.environ["REPRO_AUTOTUNE_CACHE"] = "0"
        import sys; sys.path.insert(0, {src!r})
        import jax, numpy as np, jax.numpy as jnp
        from repro.core import bnn_model
        from repro.core.bnn_model import BConv, FloatDense, Pool
        from repro.launch.mesh import make_host_mesh
        from repro.serving import InferenceServer, PhoneBitEngine

        spec = [BConv(3, 32, kernel=3, stride=1, pad=1, first=True),
                Pool(2, 2), FloatDense(8 * 8 * 32, 10)]
        params = bnn_model.init_params(jax.random.key(0), spec)
        engine = PhoneBitEngine.from_trained(params, spec, (16, 16))
        mesh = make_host_mesh(data=4, model=1)

        sharded = InferenceServer(engine, buckets=(1, 2, 4, 8),
                                  max_batch=8, mesh=mesh)
        # buckets rounded up to shard evenly over data=4
        assert sharded.scheduler.buckets == (4, 8), \\
            sharded.scheduler.buckets
        assert sharded.data_parallel == 4
        single = InferenceServer(engine, buckets=(4, 8), max_batch=8)
        sharded.compile_buckets()
        single.compile_buckets()
        before = engine.trace_count

        rng = np.random.default_rng(0)
        imgs = [rng.integers(0, 256, (16, 16, 3), dtype=np.uint8)
                for _ in range(8)]
        rs = [sharded.submit(i) for i in imgs]
        ru = [single.submit(i) for i in imgs]
        sharded.drain(); single.drain()
        assert engine.trace_count == before    # both paths precompiled
        for a, b in zip(rs, ru):
            np.testing.assert_array_equal(a.result, b.result)
        m = sharded.metrics()
        assert m["served"] == 8 and m["data_parallel"] == 4
        print("sharded-serving-ok")
    """).format(src=str(REPO / "src"))
    r = subprocess.run([sys.executable, "-c", script],
                       capture_output=True, text=True, timeout=420,
                       env=dict(os.environ))
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    assert "sharded-serving-ok" in r.stdout


# --------------------------------------------------------------------------
# LM server speaks the same protocol
# --------------------------------------------------------------------------

def test_lm_server_protocol():
    from repro.distributed.sharding import rules_for_mesh
    from repro.launch.mesh import make_host_mesh
    from repro.models import transformer
    from repro.serving.lm_server import LMServer

    cfg = transformer.LMConfig(
        name="proto-demo", n_layers=1, d_model=64, n_heads=2, n_kv_heads=1,
        d_head=32, d_ff=128, vocab=128, tie_embeddings=True)
    mesh = make_host_mesh(data=1, model=1)
    rules = rules_for_mesh(mesh)
    with mesh:
        params = transformer.init_params(jax.random.key(0), cfg, ep=1)
        server = LMServer(cfg=cfg, rules=rules, params=params, n_slots=2,
                          max_seq=32)
        assert isinstance(server, Server)
        rng = np.random.default_rng(0)
        reqs = [server.submit(list(rng.integers(1, cfg.vocab, 4)),
                              max_new=3) for _ in range(3)]
        done = server.drain()
        assert len(done) == 3 and all(r.done for r in reqs)
        assert all(1 <= len(r.result) <= 3 for r in reqs)
        m = server.metrics()
        assert m["served"] == 3 and m["dropped"] == 0
        assert m["queue_depth"] == 0 and m["p50_ms"] is not None
        # invalid requests resolve ``rejected`` at the protocol edge —
        # structured outcome, not an exception (DESIGN.md §11.2)
        bad = server.submit(list(range(1, 31)), max_new=8)
        assert bad.done and bad.outcome == "rejected"
        assert "max_seq" in bad.error
        bad = server.submit([])
        assert bad.done and bad.outcome == "rejected"
        assert "empty" in bad.error
        assert server.metrics()["rejected"] == 2
        assert server.queue_depth == 0       # rejects never enqueue

    # deadline shedding at admission — including mid-queue behind a
    # patient request while all KV slots are busy
    with mesh:
        server = LMServer(cfg=cfg, rules=rules, params=params, n_slots=1,
                          max_seq=32, clock=lambda: 100.0)
        patient1 = server.submit([1, 2], max_new=1, now=0.0)   # admitted
        patient2 = server.submit([3, 4], max_new=1, now=0.0)   # queued
        hasty = server.submit([5], max_new=1, deadline_s=1.0, now=0.0)
        server.drain()
        assert hasty.done and hasty.result is None    # shed mid-queue
        assert patient1.result and patient2.result
        m = server.metrics()
        assert m["dropped"] == 1 and m["served"] == 2
