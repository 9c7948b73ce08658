"""AOT artifact tests (DESIGN.md §12).

Export/load roundtrip (bit-exact, zero traces after load), the
per-bucket compatibility protocol (every COMPAT field mismatch falls
back to live compile with a structured ``artifact.miss`` event),
integrity failures raising a clean :class:`ArtifactError` instead of an
XLA abort, the autotune winner table riding along, and the end-to-end
pin: a **fresh subprocess** boots ``InferenceServer(artifact=...)`` and
serves submit→result with ``trace_count == 0``.
"""

import hashlib
import json
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
from repro.obs import metrics as obs_metrics
from repro.serving import (ArtifactError, InferenceServer, PhoneBitEngine,
                           export_artifact, load_artifact, read_meta)

REPO = pathlib.Path(__file__).resolve().parent.parent

SPEC = [BConv(3, 16, kernel=3, stride=1, pad=1, first=True),
        Pool(2, 2), FloatDense(8 * 8 * 16, 10)]
# With a conv after the first, whose backend the autotuner times (the
# first conv takes the MXU lowering untimed).
TUNED_SPEC = [BConv(3, 16, kernel=3, stride=1, pad=1, first=True),
              Pool(2, 2), BConv(16, 16, kernel=3, stride=1, pad=1),
              FloatDense(8 * 8 * 16, 10)]


def _engine(mode: str = "xla", spec=SPEC) -> PhoneBitEngine:
    params = bnn_model.init_params(jax.random.key(0), spec)
    return PhoneBitEngine.from_trained(params, spec, (16, 16),
                                       matmul_mode=mode)


def _imgs(n: int) -> np.ndarray:
    rng = np.random.default_rng(0)
    return rng.integers(0, 256, (n, 16, 16, 3), dtype=np.uint8)


# --------------------------------------------------------------------------
# roundtrip
# --------------------------------------------------------------------------

class TestRoundtrip:
    def test_bitexact_and_zero_traces(self, tmp_path):
        src = _engine()
        meta = export_artifact(src, tmp_path / "art", buckets=(1, 2))
        assert meta["schema"] == "phonebit-aot-v1"
        assert sorted(meta["buckets"]) == ["1", "2"]

        dst = _engine()
        with obs_metrics.use_registry() as reg:
            rep = load_artifact(dst, tmp_path / "art")
        assert rep["loaded"] == [1, 2] and not rep["missed"]
        assert reg.counter("artifact.hit").value == 2
        assert [e["outcome"] for e in reg.events("artifact")] == \
            ["hit", "hit"]

        x = _imgs(2)
        want = np.asarray(src.compile(2, donate_input=True)(
            jnp.asarray(x)))
        got = np.asarray(dst.compile(2, donate_input=True)(
            jnp.asarray(x)))
        np.testing.assert_array_equal(got, want)
        assert dst.trace_count == 0     # never traced anything

    def test_server_artifact_kwarg(self, tmp_path):
        export_artifact(_engine(), tmp_path / "art", buckets=(1, 2))
        eng = _engine()
        server = InferenceServer(eng, artifact=str(tmp_path / "art"),
                                 buckets=(1, 2), max_batch=2,
                                 max_wait_s=0.0)
        assert server.artifact_report["loaded"] == [1, 2]
        rs = [server.submit(i) for i in _imgs(3)]
        server.drain()
        assert [r.outcome for r in rs] == ["served"] * 3
        assert eng.trace_count == 0

    def test_read_meta_missing_dir(self, tmp_path):
        with pytest.raises(ArtifactError, match="not an artifact"):
            read_meta(tmp_path / "nope")


# --------------------------------------------------------------------------
# compatibility: every COMPAT field mismatch is a per-bucket miss
# --------------------------------------------------------------------------

class TestCompatFallback:
    @pytest.mark.parametrize("field,value", [
        ("schema", "phonebit-aot-v0"),
        ("device_kind", "tpu:TPU v9"),
        ("jax", "0.0.1"),
        ("mode", "vpu"),
        ("donate_input", False),
    ])
    def test_meta_mismatch_falls_back_per_bucket(self, tmp_path, field,
                                                 value):
        export_artifact(_engine(), tmp_path / "art", buckets=(1, 2))
        meta_path = tmp_path / "art" / "meta.json"
        meta = json.loads(meta_path.read_text())
        meta[field] = value
        meta_path.write_text(json.dumps(meta))

        dst = _engine()
        with obs_metrics.use_registry() as reg:
            rep = load_artifact(dst, tmp_path / "art")
        assert rep["loaded"] == []
        assert sorted(rep["missed"]) == [1, 2]
        assert all(any(field in reason for reason in reasons)
                   for reasons in rep["missed"].values())
        evs = reg.events("artifact")
        assert [e["outcome"] for e in evs] == ["miss", "miss"]
        assert {e["bucket"] for e in evs} == {1, 2}
        assert reg.counter("artifact.miss").value == 2
        # Boot still succeeds: the bucket live-compiles on first use.
        out = dst.compile(1, donate_input=True)(jnp.asarray(_imgs(1)))
        assert np.asarray(out).shape == (1, 10)
        assert dst.trace_count == 1     # the fallback traced once

    def test_graph_fingerprint_mismatch(self, tmp_path):
        export_artifact(_engine(), tmp_path / "art", buckets=(1,))
        other_spec = [BConv(3, 32, kernel=3, stride=1, pad=1, first=True),
                      Pool(2, 2), FloatDense(8 * 8 * 32, 10)]
        params = bnn_model.init_params(jax.random.key(0), other_spec)
        dst = PhoneBitEngine.from_trained(params, other_spec, (16, 16))
        rep = load_artifact(dst, tmp_path / "art")
        assert rep["loaded"] == []
        assert any("fingerprint" in r for r in rep["missed"][1])

    def test_bucket_subset_load(self, tmp_path):
        export_artifact(_engine(), tmp_path / "art", buckets=(1, 2, 4))
        dst = _engine()
        rep = load_artifact(dst, tmp_path / "art", buckets=(2,))
        assert rep["loaded"] == [2] and not rep["missed"]


# --------------------------------------------------------------------------
# integrity: corrupt bytes never reach XLA
# --------------------------------------------------------------------------

class TestIntegrity:
    def test_corrupted_bytes_raise_artifact_error(self, tmp_path):
        export_artifact(_engine(), tmp_path / "art", buckets=(1,))
        blob = tmp_path / "art" / "b1.fwd.bin"
        data = bytearray(blob.read_bytes())
        data[len(data) // 2] ^= 0xFF
        blob.write_bytes(bytes(data))
        with pytest.raises(ArtifactError, match="corrupted"):
            load_artifact(_engine(), tmp_path / "art")

    def test_undeserializable_bytes_raise_artifact_error(self, tmp_path):
        # sha-valid garbage: the checksum passes, unpickling must not
        # escape as a raw exception (and never abort into XLA).
        export_artifact(_engine(), tmp_path / "art", buckets=(1,))
        blob = tmp_path / "art" / "b1.fwd.bin"
        blob.write_bytes(b"not a pickle at all")
        meta_path = tmp_path / "art" / "meta.json"
        meta = json.loads(meta_path.read_text())
        meta["buckets"]["1"]["sha256"] = hashlib.sha256(
            b"not a pickle at all").hexdigest()
        meta_path.write_text(json.dumps(meta))
        with pytest.raises(ArtifactError, match="undeserializable"):
            load_artifact(_engine(), tmp_path / "art")

    def test_missing_executable_raises(self, tmp_path):
        export_artifact(_engine(), tmp_path / "art", buckets=(1,))
        (tmp_path / "art" / "b1.fwd.bin").unlink()
        with pytest.raises(ArtifactError, match="missing"):
            load_artifact(_engine(), tmp_path / "art")


# --------------------------------------------------------------------------
# autotune winner table rides along
# --------------------------------------------------------------------------

def test_autotune_table_rides_along(tmp_path, monkeypatch):
    from repro.runtime.autotune import Autotuner
    from repro.serving.artifact import load_autotune_table

    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", "0")   # no disk warm start
    src = _engine(mode="auto", spec=TUNED_SPEC)
    export_artifact(src, tmp_path / "art", buckets=(1,))
    assert (tmp_path / "art" / "autotune.json").exists()
    # Adoption is checked against an ISOLATED tuner: the engine's own
    # tuner shares the process-wide module caches, which a same-process
    # load already holds (the table matters on a fresh boot).
    tuner = Autotuner(cache={}, agnostic_cache={}, persist=False)
    adopted = load_autotune_table(tmp_path / "art", tuner)
    assert adopted > 0
    assert tuner.cache and tuner.agnostic_cache
    assert all(e.get("env") for e in tuner.cache.values())
    # A stale-environment table is skipped entirely, like a stale disk.
    table_path = tmp_path / "art" / "autotune.json"
    table = json.loads(table_path.read_text())
    for e in table.values():
        e["env"] = {"jax": "0.0.1", "jaxlib": "0.0.1"}
    table_path.write_text(json.dumps(table))
    assert load_autotune_table(tmp_path / "art",
                               Autotuner(cache={}, agnostic_cache={},
                                         persist=False)) == 0


# --------------------------------------------------------------------------
# the zero-warmup pin, end to end in a fresh process
# --------------------------------------------------------------------------

def test_fresh_subprocess_serves_with_zero_traces(tmp_path):
    export_artifact(_engine(), tmp_path / "art", buckets=(1, 2))
    script = textwrap.dedent("""
        import os
        os.environ["REPRO_AUTOTUNE_CACHE"] = "0"
        import sys; sys.path.insert(0, {src!r})
        import jax, numpy as np, jax.numpy as jnp
        from repro.core import bnn_model
        from repro.core.bnn_model import BConv, FloatDense, Pool
        from repro.serving import InferenceServer, PhoneBitEngine

        SPEC = [BConv(3, 16, kernel=3, stride=1, pad=1, first=True),
                Pool(2, 2), FloatDense(8 * 8 * 16, 10)]
        params = bnn_model.init_params(jax.random.key(0), SPEC)
        eng = PhoneBitEngine.from_trained(params, SPEC, (16, 16))
        server = InferenceServer(eng, artifact={art!r}, buckets=(1, 2),
                                 max_batch=2, max_wait_s=0.0)
        assert server.artifact_report["loaded"] == [1, 2], \\
            server.artifact_report

        rng = np.random.default_rng(0)
        imgs = [rng.integers(0, 256, (16, 16, 3), dtype=np.uint8)
                for _ in range(3)]
        rs = [server.submit(i) for i in imgs]
        server.drain()
        assert all(r.outcome == "served" for r in rs), \\
            [r.outcome for r in rs]
        # THE pin: submit -> result in a process that never traced.
        assert eng.trace_count == 0, eng.trace_count

        # Bit-exact vs a live-compiled reference engine (same seed).
        # Bucket-matched: 3 requests through max_batch=2 serve as a
        # batch of 2 then a batch of 1, and float accumulation order
        # differs across batch shapes — so each request is compared
        # against a reference computed at its own bucket.
        ref_eng = PhoneBitEngine.from_trained(
            bnn_model.init_params(jax.random.key(0), SPEC), SPEC,
            (16, 16))
        ref2 = np.asarray(ref_eng.compile(2)(
            jnp.asarray(np.stack(imgs[:2]))))
        ref1 = np.asarray(ref_eng.compile(1)(
            jnp.asarray(np.stack(imgs[2:]))))
        np.testing.assert_array_equal(np.asarray(rs[0].result), ref2[0])
        np.testing.assert_array_equal(np.asarray(rs[1].result), ref2[1])
        np.testing.assert_array_equal(np.asarray(rs[2].result), ref1[0])
        assert eng.trace_count == 0    # the reference traced, not us
        print("zero-warmup-ok")
    """).format(src=str(REPO / "src"), art=str(tmp_path / "art"))
    r = subprocess.run([sys.executable, "-c", script],
                       capture_output=True, text=True, timeout=420,
                       env=dict(os.environ))
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    assert "zero-warmup-ok" in r.stdout
