"""Per-node backend + tile-shape autotuning (DESIGN.md §4.6, §5.4).

All executor backends are bit-exact, so the fastest one per node is a free
win — but the winner depends on shape: popcount formulations win when the
packed reduction dim is long relative to the matmul engine's tile economics,
±1-matmul wins for fat output dims, and the direct (im2col-free) conv
kernel wins whenever patch traffic would dominate — except for large K on
tiny spatial grids, where the im2col matmul's tiling amortizes better
(the crossover benchmarks measure this globally; here it is decided *per
node*).  For the direct backends the kernel's tile shape
``(block_h, block_w, block_n)`` is part of the search space: each backend
candidate is timed over a small shape-derived sweep and the winning tile
rides along with the winning backend.

:class:`Autotuner` times each candidate on a zero-filled input of the
node's inferred shape (timing is layout/shape-dependent, not
value-dependent — binary kernels have no data-dependent control flow) and
caches the winner under a shape/attr/device signature.  The cache is keyed
so structurally identical layers across graphs (or across engine restarts
sharing a cache dict) reuse measurements instead of re-timing, and the
resulting backend map is frozen into a new :class:`GraphExecutor` — so the
serving path never re-times or re-compiles.

Bucketed serving tunes the same graph at several batch sizes
(``PhoneBitEngine.compile`` per bucket).  A winner measured at one batch
is usually still the winner at another — the reduction geometry per
example is unchanged — *except* when the winning tile spans the batch dim
(``block_n``).  So each fresh measurement is additionally recorded under a
batch-agnostic signature (batch dim replaced by a placeholder), and a
cache miss at a new batch size first consults that record: if the winner's
tile carries no ``block_n``, it is adopted without re-timing (the entry is
marked ``reused_across_batch`` so reports can tell a measured winner from
an inherited one).  Batch-agnostic records persist to disk alongside the
exact ones under a ``batchless::`` key prefix.

A first binary conv over uint8 pixels is not timed: it takes the
executor's ``mxu_image`` lowering by its layer type (DESIGN.md §3.6).

Fused regions (DESIGN.md §9) get their own sweep: :meth:`tune_chains`
times the chain megakernel over per-chain tile shapes — a new search
space, since a chain tile couples every stage through halo growth — and
caches winners under ``chain::``-prefixed chain-shaped signatures (stage
specs + entry shape + device kind) in the same stores.

The cache additionally persists to disk (``<repo>/.cache/repro/autotune.json``,
keyed by the same signatures — which embed the device kind) so repeated
engine startups skip re-timing entirely.  ``REPRO_AUTOTUNE_CACHE=0``
disables persistence; any other value overrides the cache file path.
"""

from __future__ import annotations

import json
import os
import pathlib
import tempfile
import time
from typing import Iterable

import jax
import jax.numpy as jnp
import numpy as np

from repro.cache import AUTOTUNE_FILE
from repro.obs import metrics as _obs_metrics
from repro.obs import trace as _trace
from repro.runtime.executor import (BACKENDS, IMAGE_BACKEND, GraphExecutor,
                                    eval_node, valid_backends)
from repro.runtime.graph import (DISPATCHABLE_OPS, Graph, image_conv_expand,
                                 infer_types)

_CACHE_ENV = "REPRO_AUTOTUNE_CACHE"

# Default candidates: the pure-XLA formulations everywhere; the Pallas
# kernels only compete where they are compiled (on TPU) — in interpret mode
# they are validators, not contenders.
def default_candidates() -> tuple[str, ...]:
    if jax.default_backend() == "tpu":
        return BACKENDS
    return ("xla", "xla_pm1")


def cache_path() -> pathlib.Path | None:
    """Resolved on-disk cache location; None when persistence is off."""
    val = os.environ.get(_CACHE_ENV)
    if val == "0":
        return None
    if val:
        return pathlib.Path(val).expanduser()
    return AUTOTUNE_FILE


def _env_stamp() -> dict:
    """The provenance stamp every cache entry carries (DESIGN.md §12.3):
    jax/jaxlib versions.  The device kind is already part of the
    signature; the *toolchain* version was not — winners tuned under one
    jax silently applied under another.  Stamped at measurement time and
    checked at disk-lookup time (:func:`entry_env_ok`)."""
    try:
        import jaxlib

        jaxlib_version = jaxlib.__version__
    except Exception:                   # noqa: BLE001 — stamp best-effort
        jaxlib_version = None
    return {"jax": jax.__version__, "jaxlib": jaxlib_version}


def entry_env_ok(entry) -> bool:
    """Whether a persisted tuning entry was measured under this process's
    toolchain.  Unstamped (pre-stamp) entries are stale by definition."""
    return isinstance(entry, dict) and entry.get("env") == _env_stamp()


def _device_kind() -> str:
    """Concrete accelerator model (e.g. 'TPU v4'), not just the platform:
    tile winners tuned for one VMEM/lane geometry must not warm-start a
    different generation."""
    try:
        return f"{jax.default_backend()}:{jax.devices()[0].device_kind}"
    except (IndexError, RuntimeError):
        return jax.default_backend()


def _node_signature(node, in_shape: tuple[int, ...],
                    candidates: tuple[str, ...] = ()) -> str:
    """Stable string key: op + static attrs + shapes + candidate set +
    device kind (strings so the cache round-trips through JSON)."""
    attrs = tuple(sorted((k, v) for k, v in node.attrs.items()
                         if isinstance(v, (int, bool, str, tuple))))
    pshapes = tuple(sorted(
        (k, tuple(np.shape(v))) for k, v in node.params.items()
        if not hasattr(v, "_fields")))
    return repr((node.op, attrs, tuple(in_shape), pshapes, candidates,
                 _device_kind()))


def _agnostic_signature(node, in_shape: tuple[int, ...],
                        candidates: tuple[str, ...] = ()) -> str:
    """Batch-agnostic variant of :func:`_node_signature`: the batch dim is
    replaced by a placeholder so winners can transfer across serving
    buckets (valid unless the winning tile spans the batch — ``block_n``)."""
    return "batchless::" + _node_signature(
        node, ("B",) + tuple(in_shape[1:]), candidates)


def _out_rows(node, in_shape: tuple[int, ...]) -> int:
    """Final output rows of a conv(/pool) node — what block_h tiles."""
    from repro.core.binary_conv import conv_out_size

    a = node.attrs
    oh = conv_out_size(in_shape[1], a["kernel"], a["stride"], a["pad"])
    if node.op == "packed_conv_pool":
        pp = sum(a.get("pool_pad", (0, 0)))
        oh = (oh + pp - a["pool_window"]) // a["pool_stride"] + 1
    return max(oh, 1)


def _direct_fits(backend: str, node, in_shape: tuple[int, ...],
                 tile: dict) -> bool:
    """Whether a direct-kernel tile's VMEM footprint fits the kernel's
    budget (the chain kernel's accounting, :mod:`repro.kernels.chain_conv`)."""
    from repro.kernels.chain_conv import plan_tile
    from repro.kernels.direct_conv_bn_binarize import direct_stages

    a = node.attrs
    pool = backend == "vpu_direct_pool" and "pool_window" in a
    stages = direct_stages(
        a["kernel"], a["stride"], a["pad"], a["channels"],
        a["pool_window"] if pool else None,
        a.get("pool_stride"), tuple(a.get("pool_pad", (0, 0))))
    return plan_tile(stages, in_shape, tile.get("block_h"),
                     tile.get("block_w"), tile.get("block_n", 1)).fits()


def _tile_candidates(backend: str, node,
                     in_shape: tuple[int, ...]) -> list[dict]:
    """Shape-derived (block_h, block_w, block_n) sweep for the direct
    kernels; the im2col backends have no per-node tile knobs here.
    Candidates are expressed in *effective* (clamped) tile sizes and
    deduplicated so no configuration is compiled or timed twice.  Every
    candidate is legal on the TPU: a split width is a multiple of 8 (the
    sublane tiling), and the footprint fits the VMEM budget."""
    if backend not in ("vpu_direct", "vpu_direct_pool"):
        return [{}]
    n, fh = in_shape[0], _out_rows(node, in_shape)
    cands: list[dict] = [{}]
    seen: set[int] = set()
    for bh in (4, 8, 16, fh):
        eff = min(bh, fh)
        if eff not in seen:
            seen.add(eff)
            cands.append({"block_h": eff})
    if fh > 8:
        cands.append({"block_h": min(8, fh), "block_w": 8})
    if n > 1:
        cands.append({"block_n": n})
    return [t for t in cands
            if not t or _direct_fits(backend, node, in_shape, t)]


def _label(backend: str, tile: dict) -> str:
    if not tile:
        return backend
    inner = ",".join(f"{k.replace('block_', '')}{v}"
                     for k, v in sorted(tile.items()))
    return f"{backend}[{inner}]"


def _tuning_event(outcome: str, op: str, key: str, entry: dict) -> None:
    """Record one tuning decision in the process registry: an
    ``autotune.{hit,disk_hit,disk_miss,xfer_hit,miss}`` counter bump plus a
    structured ``autotune`` event carrying the signature and, for fresh
    sweeps, how many candidates were timed."""
    reg = _obs_metrics.get_registry()
    reg.counter(f"autotune.{outcome}").inc()
    reg.event("autotune", outcome=outcome, op=op, signature=key,
              sweep_size=(len(entry.get("timings_ms", {}))
                          if outcome == "miss" else 0))


def _chain_signature(chain) -> str:
    """Chain-shaped cache key: the stage-spec tuple + head input shape +
    device kind, ``chain::``-prefixed so per-node and per-chain records
    share one disk cache without colliding."""
    return "chain::" + repr((chain.signature_key(), _device_kind()))


def _chain_tile_candidates(chain) -> list[dict]:
    """Per-chain tile sweep.  Chain tiles couple the stages through halo
    growth (a smaller final tile shrinks every interior tile but raises
    the recompute overlap fraction), so the sweep is over the *final*
    tile: the tallest band that fits (the default; the whole map when
    it fits, which needs no recompute), a few spatial splits, and a
    batch-spanning tile.  Candidates whose VMEM plan no longer fits the
    chain's budget are dropped before timing."""
    from repro.kernels.chain_conv import chain_geometry

    n, h, w = chain.in_shape[0], chain.in_shape[1], chain.in_shape[2]
    fh = chain_geometry(chain.stages, h, w, None, None).final_hw[0]
    cands: list[dict] = [{}]
    seen = {fh}
    for bh in (4, 8, 16, max(1, fh // 2)):
        eff = min(bh, fh)
        if eff not in seen:
            seen.add(eff)
            cands.append({"block_h": eff})
    if n > 1:
        cands.append({"block_n": n})
    return [t for t in cands if chain.tiling(t).vmem.fits()]


class Autotuner:
    """Times candidates once per node signature; caches winners in memory
    and (by default) on disk."""

    def __init__(self, cache: dict | None = None,
                 candidates: Iterable[str] | None = None,
                 warmup: int = 1, iters: int = 3, persist: bool = True,
                 agnostic_cache: dict | None = None):
        self.cache: dict = cache if cache is not None else {}
        # batch-agnostic winners (``batchless::`` keys), kept out of
        # ``cache`` so its per-node-signature shape stays 1:1.
        self.agnostic_cache: dict = (agnostic_cache
                                     if agnostic_cache is not None else {})
        self.candidates = tuple(candidates if candidates is not None
                                else default_candidates())
        for c in self.candidates:
            if c not in BACKENDS:
                raise ValueError(f"unknown candidate backend {c!r}")
        self.warmup = warmup
        self.iters = iters
        # persist=False forces fresh measurements and writes nothing —
        # what benchmarks use so reported timings are from *this* run.
        self.persist = persist
        self._disk: dict = self._load_disk() if persist else {}

    # ---- persistence -----------------------------------------------------
    def _load_disk(self) -> dict:
        path = cache_path()
        if path is None or not path.exists():
            return {}
        try:
            with open(path) as f:
                return json.load(f)
        except (OSError, json.JSONDecodeError):
            return {}

    def _save_disk(self, new_entries: dict) -> None:
        path = cache_path()
        if path is None or not new_entries or not self.persist:
            return
        try:
            path.parent.mkdir(parents=True, exist_ok=True)
            merged = dict(self._load_disk())
            merged.update(new_entries)
            fd, tmp = tempfile.mkstemp(dir=path.parent, suffix=".tmp")
            with os.fdopen(fd, "w") as f:
                json.dump(merged, f, indent=1, sort_keys=True)
            os.replace(tmp, path)
            self._disk = merged
        except OSError:
            pass  # persistence is best-effort; tuning already succeeded

    # ---- measurement -----------------------------------------------------
    def _time_node(self, node, x, backend: str, tile: dict) -> float:
        fn = jax.jit(lambda params, xx: eval_node(
            node.op, node.attrs, params, [xx], backend=backend, tile=tile))
        for _ in range(self.warmup):
            jax.block_until_ready(fn(node.params, x))
        times = []
        for _ in range(self.iters):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(node.params, x))
            times.append(time.perf_counter() - t0)
        return float(np.median(times))

    def _tune_node(self, node, in_shape, in_dtype) -> dict:
        x = jnp.zeros(in_shape, in_dtype)
        timings: dict[str, float] = {}
        best = (float("inf"), None, {})
        for backend in self.candidates:
            if backend not in valid_backends(node.op):
                continue
            for tile in _tile_candidates(backend, node, in_shape):
                t = self._time_node(node, x, backend, tile)
                timings[_label(backend, tile)] = t
                if t < best[0]:
                    best = (t, backend, tile)
        if best[1] is None:
            raise ValueError(
                f"no candidate in {self.candidates} applies to op "
                f"{node.op!r}; include a universal backend (e.g. 'xla')")
        return dict(winner=best[1], tile=best[2],
                    timings_ms={lbl: round(t * 1e3, 4)
                                for lbl, t in timings.items()},
                    env=_env_stamp())

    def entry(self, node, in_shape: tuple[int, ...]) -> dict | None:
        """The cached tuning record for a node signature, if any."""
        return self.cache.get(
            _node_signature(node, in_shape, self.candidates))

    def tune(self, graph: Graph, input_shape: tuple[int, ...],
             ) -> dict[int, str]:
        """Pick a backend per dispatchable node; returns the backend map.
        (:meth:`tune_with_tiles` also returns the per-node tile shapes.)"""
        return self.tune_with_tiles(graph, input_shape)[0]

    def _cross_batch_entry(self, akey: str) -> dict | None:
        """A winner measured at another batch size, if transferable.
        Disk records must also pass the toolchain stamp — a cross-batch
        winner from another jax version is as stale as an exact one."""
        entry = self.agnostic_cache.get(akey)
        if entry is None:
            disk = self._disk.get(akey)
            if disk is not None and entry_env_ok(disk):
                entry = disk
        if entry and not (entry.get("tile") or {}).get("block_n"):
            return entry
        return None

    def tune_with_tiles(self, graph: Graph, input_shape: tuple[int, ...],
                        ) -> tuple[dict[int, str], dict[int, dict]]:
        types = infer_types(graph, input_shape)
        choices: dict[int, str] = {}
        tiles: dict[int, dict] = {}
        fresh: dict[str, dict] = {}
        for nid in graph.topo_order():
            node = graph.nodes[nid]
            if node.op not in DISPATCHABLE_OPS:
                continue
            if image_conv_expand(graph, nid) is not None:
                # The first conv's MXU lowering goes by layer type, untimed
                # (executor module docstring).
                choices[nid] = IMAGE_BACKEND
                continue
            in_t = types[node.inputs[0]]
            key = _node_signature(node, in_t.shape, self.candidates)
            akey = _agnostic_signature(node, in_t.shape, self.candidates)
            if key in self.cache:
                outcome = "hit"             # warm in-memory winner
            elif key in self._disk and entry_env_ok(self._disk[key]):
                # warm start from a prior run under the same toolchain
                self.cache[key] = self._disk[key]
                outcome = "disk_hit"
            elif key in self._disk:
                # A winner exists on disk but was tuned under a different
                # (or unstamped) jax/jaxlib — re-sweep rather than trust it.
                _tuning_event("disk_miss", node.op, key, self._disk[key])
                with _trace.span("autotune.sweep", "autotune",
                                 op=node.op):
                    self.cache[key] = fresh[key] = self._tune_node(
                        node, in_t.shape, in_t.dtype)
                outcome = "miss"
            elif (xfer := self._cross_batch_entry(akey)) is not None:
                # Winner measured at another serving bucket; tile has
                # no block_n, so it transfers without re-timing.
                self.cache[key] = dict(xfer, reused_across_batch=True)
                outcome = "xfer_hit"
            else:
                with _trace.span("autotune.sweep", "autotune",
                                 op=node.op):
                    self.cache[key] = fresh[key] = self._tune_node(
                        node, in_t.shape, in_t.dtype)
                outcome = "miss"
            entry = self.cache[key]
            _tuning_event(outcome, node.op, key, entry)
            if akey not in self.agnostic_cache and \
                    not entry.get("reused_across_batch"):
                record = {k: v for k, v in entry.items()
                          if k != "reused_across_batch"}
                self.agnostic_cache[akey] = record
                if key in fresh:
                    fresh[akey] = record
            choices[nid] = entry["winner"]
            tile = entry.get("tile") or {}
            if tile:
                tiles[nid] = dict(tile)
        self._save_disk(fresh)
        return choices, tiles

    def tuned_executor(self, graph: Graph, input_shape: tuple[int, ...],
                       donate_input: bool = False) -> GraphExecutor:
        choices, tiles = self.tune_with_tiles(graph, input_shape)
        return GraphExecutor(graph, choices, tiles,
                             donate_input=donate_input)

    # ---- chain (region) tuning -------------------------------------------
    def _time_chain(self, chain, stage_arrays, x, tile: dict) -> float:
        from repro.kernels import ops as kops

        kw = chain.tiling(tile).kernel_args()
        fn = jax.jit(lambda arrs, xx: kops.chain_forward(
            xx, chain.stages, arrs, **kw))
        for _ in range(self.warmup):
            jax.block_until_ready(fn(stage_arrays, x))
        times = []
        for _ in range(self.iters):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(stage_arrays, x))
            times.append(time.perf_counter() - t0)
        return float(np.median(times))

    def _tune_chain(self, chain, graph: Graph) -> dict:
        from repro.runtime.regions import chain_stage_arrays

        arrays = chain_stage_arrays(
            chain, {str(nid): graph.nodes[nid].params
                    for nid in chain.node_ids})
        x = jnp.zeros(chain.in_shape, jnp.int32)
        timings: dict[str, float] = {}
        best = (float("inf"), {})
        for tile in _chain_tile_candidates(chain):
            t = self._time_chain(chain, arrays, x, tile)
            timings[_label("vpu_chain", tile)] = t
            if t < best[0]:
                best = (t, tile)
        return dict(winner="vpu_chain", tile=best[1],
                    timings_ms={lbl: round(t * 1e3, 4)
                                for lbl, t in timings.items()},
                    env=_env_stamp())

    def tune_chains(self, graph: Graph, chains) -> None:
        """Pick a tile shape per chain (set in place on ``chain.tile``).
        Winners cache/persist under chain-shaped ``chain::`` signatures —
        structurally identical regions across graphs or restarts reuse
        the measurement, exactly like per-node winners."""
        fresh: dict[str, dict] = {}
        for chain in chains:
            key = _chain_signature(chain)
            if key in self.cache:
                outcome = "hit"
            elif key in self._disk and entry_env_ok(self._disk[key]):
                self.cache[key] = self._disk[key]
                outcome = "disk_hit"
            else:
                if key in self._disk:
                    _tuning_event("disk_miss", "chain", key,
                                  self._disk[key])
                with _trace.span("autotune.sweep", "autotune", op="chain"):
                    self.cache[key] = fresh[key] = self._tune_chain(
                        chain, graph)
                outcome = "miss"
            _tuning_event(outcome, "chain", key, self.cache[key])
            tile = dict(self.cache[key].get("tile") or {})
            # The signature does not embed the VMEM budget, so a winner
            # cached under a larger budget may no longer fit this
            # chain's: re-check, and degrade to the default tile (which
            # region formation already proved fits) rather than compile
            # an over-budget arena.
            if tile and not chain.tiling(tile).vmem.fits():
                tile = {}
            chain.tile = tile
        self._save_disk(fresh)
