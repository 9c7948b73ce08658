"""AOT executable artifacts: pay the compile cost offline (DESIGN.md §12).

PhoneBit's deployment story (Fig 2) is that everything expensive — layout,
layer integration, kernel selection — happens once, offline, and the
device only ever runs the optimized binary path.  The serving stack
honors that for *tracing* (per-bucket executable cache) but still pays
full trace + XLA compile on every process boot.  This module closes the
gap: :func:`export_artifact` serializes every compiled bucket executable
via JAX AOT (``jax.jit(...).lower(...).compile()`` +
``jax.experimental.serialize_executable``) into one versioned directory,
together with the autotune winner table, the backend/memory report, and
a provenance meta block; :func:`load_artifact` restores them into an
engine's per-bucket executable cache with **zero serve-time traces**
(``engine.trace_count == 0`` after load — the executables never pass
through ``jax.jit`` tracing at all).

Artifact layout (one directory)::

    artifact/
      meta.json        schema + provenance + compat fields + bucket index
      autotune.json    the winner table (exact + batchless + chain:: keys)
      b{N}.fwd.bin     pickled (payload, in_tree, out_tree) per bucket
      b{N}.head.bin    the workload postprocess head, when exported

Compatibility policy (DESIGN.md §12.2): *environment* mismatches —
artifact schema version, device kind, jax/jaxlib version, engine mode,
graph fingerprint, donation/data-parallel flags — degrade **per bucket**
to the live compile path, each recorded as a structured ``artifact``
event with ``outcome="miss"`` and counted on ``artifact.miss`` (boot
still succeeds, just slower).  *Integrity* failures — checksum mismatch,
unpicklable or undeserializable executable bytes — raise a clean
:class:`ArtifactError` instead of handing corrupt bytes to XLA.

Export is restricted to ``data_parallel == 1`` executables: a sharded
executable bakes in the exporting host's device mesh, which is exactly
the kind of silent environment coupling the meta block exists to refuse.
"""

from __future__ import annotations

import hashlib
import json
import pathlib
import pickle
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import metrics as _obs_metrics
from repro.obs import trace as _trace
from repro.serving import faults as _faults

ARTIFACT_SCHEMA = "phonebit-aot-v1"
_META = "meta.json"
_AUTOTUNE = "autotune.json"

#: The meta fields a loading process must match bucket-for-bucket; a
#: mismatch on any of them is a per-bucket ``artifact.miss`` (DESIGN.md
#: §12.2), never an error.
COMPAT_FIELDS = ("schema", "device_kind", "jax", "mode", "fingerprint",
                 "donate_input", "data_parallel")


class ArtifactError(RuntimeError):
    """An artifact is unreadable or fails integrity checks (corrupted
    executable bytes, bad checksum, missing files).  Environment
    mismatches are NOT errors — they fall back per bucket."""


# ---------------------------------------------------------------------------
# meta / fingerprints
# ---------------------------------------------------------------------------

def _device_kind() -> str:
    try:
        return f"{jax.default_backend()}:{jax.devices()[0].device_kind}"
    except (IndexError, RuntimeError):
        return jax.default_backend()


def graph_fingerprint(graph) -> str:
    """Stable digest of the serving graph's *structure*: ops, static
    attrs, edges, and parameter shapes/dtypes (not values — the artifact
    stores executables, weights stay live operands).  A code change that
    alters lowering changes the fingerprint, so a stale artifact misses
    instead of feeding a mismatched operand pytree to a frozen
    executable."""
    rows = []
    for nid in graph.topo_order():
        node = graph.nodes[nid]
        attrs = tuple(sorted(
            (k, v) for k, v in node.attrs.items()
            if isinstance(v, (int, bool, str, tuple))))
        pshapes = []
        for k, v in sorted(node.params.items()):
            if hasattr(v, "_fields"):           # IntegratedParams
                for f in v._fields:
                    fv = getattr(v, f)
                    pshapes.append((k + "." + f, tuple(np.shape(fv)),
                                    str(np.asarray(fv).dtype)))
            else:
                pshapes.append((k, tuple(np.shape(v)),
                                str(np.asarray(v).dtype)))
        rows.append((nid, node.op, attrs, tuple(node.inputs),
                     tuple(pshapes)))
    return hashlib.sha256(repr(rows).encode()).hexdigest()[:16]


def _env_meta(engine, *, donate_input: bool, data_parallel: int) -> dict:
    from repro.obs.provenance import provenance_meta

    return {
        "schema": ARTIFACT_SCHEMA,
        "device_kind": _device_kind(),
        "jax": jax.__version__,
        "mode": engine.matmul_mode,
        "fingerprint": graph_fingerprint(engine._graph),
        "donate_input": bool(donate_input),
        "data_parallel": int(data_parallel),
        "input_hw": list(engine.input_hw),
        "provenance": provenance_meta(),
    }


def _sha256(path: pathlib.Path) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 20), b""):
            h.update(chunk)
    return h.hexdigest()


# ---------------------------------------------------------------------------
# serialization primitives
# ---------------------------------------------------------------------------

def _serialize_compiled(compiled, path: pathlib.Path) -> str:
    """Serialize one AOT-compiled executable (payload + arg pytree defs)
    to ``path``; returns its sha256."""
    from jax.experimental import serialize_executable as _se

    payload, in_tree, out_tree = _se.serialize(compiled)
    with open(path, "wb") as f:
        pickle.dump({"payload": payload, "in_tree": in_tree,
                     "out_tree": out_tree}, f)
    return _sha256(path)


def _deserialize_compiled(path: pathlib.Path, want_sha: str):
    """Integrity-checked inverse of :func:`_serialize_compiled`.  Any
    failure — checksum, unpickling, XLA deserialization — surfaces as
    :class:`ArtifactError` before corrupt bytes reach the runtime."""
    from jax.experimental import serialize_executable as _se

    if not path.exists():
        raise ArtifactError(f"artifact executable missing: {path}")
    got_sha = _sha256(path)
    if got_sha != want_sha:
        raise ArtifactError(
            f"artifact executable corrupted: {path.name} sha256 "
            f"{got_sha[:12]} != recorded {want_sha[:12]}")
    try:
        with open(path, "rb") as f:
            blob = pickle.load(f)
        return _se.deserialize_and_load(
            blob["payload"], blob["in_tree"], blob["out_tree"])
    except ArtifactError:
        raise
    except Exception as e:              # noqa: BLE001 — wrap, never abort
        raise ArtifactError(
            f"artifact executable undeserializable: {path.name}: "
            f"{type(e).__name__}: {e}") from e


class AotExecutor:
    """A deserialized bucket executable behind the GraphExecutor serve
    surface (``__call__`` / ``arrays`` / ``trace_count``).

    ``trace_count`` is a constant 0 and can never increment: the
    executable was compiled offline and restored without tracing — this
    is the pin the zero-warmup tests assert end to end."""

    trace_count = 0

    def __init__(self, compiled: Callable, arrays: dict,
                 head: Callable | None = None, *, bucket: int,
                 donate_input: bool = False):
        self._compiled = compiled
        self._head = head
        self.arrays = arrays
        self.bucket = bucket
        self.donate_input = donate_input

    def __call__(self, x) -> jnp.ndarray:
        if _faults._PLAN is not None:
            _faults.maybe_fault("executor.call", bucket=self.bucket,
                                aot=True)
        out = self._compiled(self.arrays, x)
        if self._head is not None:
            out = self._head(out)
        return out


# ---------------------------------------------------------------------------
# export
# ---------------------------------------------------------------------------

def export_artifact(engine, path, buckets=(1, 2, 4, 8), *,
                    donate_input: bool = True,
                    head_fn: Callable | None = None,
                    workload: str | None = None) -> dict:
    """Serialize one AOT executable per bucket into directory ``path``.

    ``engine`` is a :class:`~repro.serving.engine.PhoneBitEngine`
    (:meth:`WorkloadEngine.export_artifact` passes its postprocess head
    as ``head_fn``, exported per bucket at the forward output shape so a
    loaded workload serves decoded predictions trace-free too).  The
    engine is compiled (and, under ``matmul_mode="auto"``, autotuned)
    live first — export is the *offline* half of the split, so paying
    trace/compile/tune here is the point.  Returns the meta block.
    """
    path = pathlib.Path(path)
    path.mkdir(parents=True, exist_ok=True)
    meta = _env_meta(engine, donate_input=donate_input, data_parallel=1)
    meta["workload"] = workload
    meta["buckets"] = {}
    report: dict[str, Any] = {}
    for bs in sorted(set(int(b) for b in buckets)):
        with _trace.span("artifact.export", "artifact", bucket=bs):
            exe = engine.compile(bs, donate_input=donate_input)
            x_sds = jax.ShapeDtypeStruct(engine._plan_shape(bs), jnp.uint8)
            lowered = exe._jitted.lower(exe.arrays, x_sds)
            entry = {"file": f"b{bs}.fwd.bin"}
            entry["sha256"] = _serialize_compiled(
                lowered.compile(), path / entry["file"])
            if head_fn is not None:
                out_info = lowered.out_info
                y_sds = jax.ShapeDtypeStruct(out_info.shape, out_info.dtype)
                entry["head_file"] = f"b{bs}.head.bin"
                entry["head_sha256"] = _serialize_compiled(
                    jax.jit(head_fn).lower(y_sds).compile(),
                    path / entry["head_file"])
            meta["buckets"][str(bs)] = entry
        report[str(bs)] = {"backends": exe.backend_report()}
    meta["report"] = report
    # The autotune winner table rides along (T-MAC's --reuse-tuned): a
    # loader whose environment misses a bucket still warm-starts its
    # live-compile fallback from these winners instead of re-timing.
    tuner = getattr(engine, "_tuner", None)
    if tuner is not None and (tuner.cache or tuner.agnostic_cache):
        with open(path / _AUTOTUNE, "w") as f:
            json.dump({**tuner.cache, **tuner.agnostic_cache}, f, indent=1,
                      sort_keys=True)
    with open(path / _META, "w") as f:
        json.dump(meta, f, indent=1, sort_keys=True)
    return meta


# ---------------------------------------------------------------------------
# load
# ---------------------------------------------------------------------------

def read_meta(path) -> dict:
    path = pathlib.Path(path)
    meta_path = path / _META
    if not meta_path.exists():
        raise ArtifactError(f"not an artifact directory: {path} "
                            f"(missing {_META})")
    try:
        with open(meta_path) as f:
            return json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        raise ArtifactError(f"unreadable artifact meta: {e}") from e


def compat_mismatches(meta: dict, engine, *, donate_input: bool,
                      data_parallel: int) -> list[str]:
    """Which :data:`COMPAT_FIELDS` differ between the artifact and this
    process/engine (empty list = fully compatible)."""
    want = _want_env(engine, donate_input=donate_input,
                     data_parallel=data_parallel)
    return [f"{k}: artifact={meta.get(k)!r} != here={want[k]!r}"
            for k in COMPAT_FIELDS if meta.get(k) != want[k]]


def _want_env(engine, *, donate_input: bool, data_parallel: int) -> dict:
    return {
        "schema": ARTIFACT_SCHEMA,
        "device_kind": _device_kind(),
        "jax": jax.__version__,
        "mode": engine.matmul_mode,
        "fingerprint": graph_fingerprint(engine._graph),
        "donate_input": bool(donate_input),
        "data_parallel": int(data_parallel),
    }


def _miss(bucket: int, reasons: list[str]) -> None:
    reg = _obs_metrics.get_registry()
    reg.counter("artifact.miss").inc()
    reg.event("artifact", outcome="miss", bucket=bucket,
              reasons=list(reasons))
    _trace.instant("artifact.miss", "artifact", bucket=bucket)


def _hit(bucket: int) -> None:
    reg = _obs_metrics.get_registry()
    reg.counter("artifact.hit").inc()
    reg.event("artifact", outcome="hit", bucket=bucket)


def load_autotune_table(path, tuner) -> int:
    """Merge the artifact's winner table into a tuner's caches (entries
    already present win; stale-environment entries are skipped exactly
    like the disk cache's).  Returns how many entries were adopted."""
    from repro.runtime.autotune import entry_env_ok

    path = pathlib.Path(path) / _AUTOTUNE
    if not path.exists():
        return 0
    try:
        with open(path) as f:
            table = json.load(f)
    except (OSError, json.JSONDecodeError):
        return 0
    adopted = 0
    for key, entry in table.items():
        if not entry_env_ok(entry):
            continue
        store = (tuner.agnostic_cache if key.startswith("batchless::")
                 else tuner.cache)
        if key not in store:
            store[key] = entry
            adopted += 1
    return adopted


def load_artifact(engine, path, *, donate_input: bool = True,
                  data_parallel: int = 1, buckets=None,
                  head: bool = False) -> dict:
    """Restore AOT bucket executables from ``path`` into ``engine``'s
    per-bucket executable cache.

    Per-bucket protocol (DESIGN.md §12.2): environment mismatch →
    structured ``artifact.miss`` event + live-compile fallback on first
    use; integrity failure → :class:`ArtifactError`.  Returns
    ``{"loaded": [buckets], "missed": {bucket: [reasons]},
    "autotune_entries": n}``.  With ``head=True`` the workload
    postprocess head is deserialized per bucket and composed onto the
    forward executable (:class:`AotExecutor`).
    """
    path = pathlib.Path(path)
    meta = read_meta(path)
    mismatches = compat_mismatches(meta, engine, donate_input=donate_input,
                                   data_parallel=data_parallel)
    tuner = getattr(engine, "_tuner", None)
    adopted = load_autotune_table(path, tuner) if tuner is not None else 0
    want = ({int(b) for b in buckets} if buckets is not None else None)
    loaded: list[int] = []
    missed: dict[int, list[str]] = {}
    arrays = None
    for bs_key, entry in sorted(meta.get("buckets", {}).items(),
                                key=lambda kv: int(kv[0])):
        bs = int(bs_key)
        if want is not None and bs not in want:
            continue
        reasons = list(mismatches)
        if head and "head_file" not in entry:
            reasons.append("head: artifact has no postprocess head")
        if reasons:
            missed[bs] = reasons
            _miss(bs, reasons)
            continue
        with _trace.span("artifact.load", "artifact", bucket=bs):
            compiled = _deserialize_compiled(path / entry["file"],
                                             entry["sha256"])
            head_fn = None
            if head and "head_file" in entry:
                head_fn = _deserialize_compiled(path / entry["head_file"],
                                                entry["head_sha256"])
            if arrays is None:
                from repro.runtime.executor import graph_operands

                # Traced operands come from the *live* engine (weights
                # are data, not part of the executable); building the
                # operand pytree lowers the graph host-side — no jit,
                # no traces.
                arrays = graph_operands(engine._graph)
            exe = AotExecutor(compiled, arrays, head_fn, bucket=bs,
                              donate_input=donate_input)
        engine._install_executable(bs, exe, donate_input=donate_input,
                                   data_parallel=data_parallel)
        loaded.append(bs)
        _hit(bs)
    return {"loaded": loaded, "missed": missed,
            "autotune_entries": adopted, "workload": meta.get("workload")}
