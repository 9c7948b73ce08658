"""PhoneBitEngine: the paper's stand-alone BNN inference engine (Fig 2/3).

Deployment flow exactly as the paper's Fig 2: a trained model (latent float
params) is converted offline — BN folded to integer thresholds, weights
bit-packed, first layer bit-plane-expanded — into the compressed artifact;
the engine loads the artifact and serves the packed integer forward.

Since the graph-runtime rework the engine executes through
:mod:`repro.runtime`: the artifact is lowered to an operator graph
(DESIGN.md §4) and evaluated by a jit-compiled topological executor whose
per-node backend is either fixed by ``matmul_mode`` or chosen by the
autotuner.  The original flat ``packed_forward`` walk is kept as the
``legacy_call`` cross-check oracle.

``matmul_mode`` values (DESIGN.md §3/§4.5/§5):

* ``"xla"``             pure-JAX xor+popcount (CPU-timeable baseline),
* ``"xla_pm1"``         pure-JAX ±1-matmul reformulation,
* ``"vpu_popcount"``    im2col Pallas kernel, paper-faithful (interpret on
                        CPU),
* ``"mxu_pm1"``         ±1 matmul routed for the TPU MXU, beyond-paper,
* ``"vpu_direct"``      direct (im2col-free) Pallas conv kernel; dense
                        layers degrade to ``vpu_popcount``,
* ``"vpu_direct_pool"`` direct kernel with the OR-pool epilogue fused in
                        (``packed_conv_pool`` nodes; others degrade),
* ``"vpu_chain"``       chain-fusion megakernel regions (DESIGN.md §9):
                        maximal runs of packed conv/pool ops execute as
                        single Pallas calls with VMEM-resident
                        intermediates at planner offsets; ops outside a
                        region degrade per-node,
* ``"auto"``            per-node autotune — backend *and* direct-kernel
                        tile shape, winners cached per shape signature and
                        persisted to disk (``REPRO_AUTOTUNE_CACHE=0``
                        opts out).

The engine always lowers through :func:`runtime.fuse_pool_epilogue`, so
conv+pool pairs serve as single ``packed_conv_pool`` nodes and the unpooled
conv map drops out of the memory plan.

Batched serving (DESIGN.md §7) goes through the **per-bucket executable
cache**: ``compile(batch_size)`` builds (once) a jit-compiled
:class:`~repro.runtime.executor.GraphExecutor` for that batch bucket and
caches it on the engine, so serve time never retraces — mixed-size request
streams are padded to bucket sizes by the scheduler and always hit an
already-compiled executable.  Under ``matmul_mode="auto"`` each bucket is
autotuned at *its own* batch shape; winners measured at one bucket
transfer to others when valid (no batch-spanning tile), so warming N
buckets costs ~one tuning pass.  ``compile`` also takes ``donate_input=``
(the serving path donates each batch's input buffer to the device) and
``data_parallel=`` (autotune at the per-device shard shape when the server
shards batches across a mesh).  ``trace_count`` aggregates over all
compiled buckets — the serve-time no-recompile contract is
``engine.trace_count`` staying flat while requests flow.  There is no
manual warm-up protocol: ``InferenceServer.compile_buckets()`` (or any
first call at a bucket) populates the cache.

API mirrors the paper's Fig 3 simplicity::

    engine = PhoneBitEngine.from_artifact("model.npz", spec, (227, 227))
    logits = engine(images_uint8)
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Sequence

import jax
import jax.numpy as jnp

from repro.core import bnn_model, converter
from repro.obs import metrics as _obs_metrics
from repro.obs import trace as _trace
from repro.serving import faults as _faults

# Modes whose flat-path impl is the ±1-matmul reformulation.
_PM1_MODES = ("mxu_pm1", "xla_pm1")
# Process-wide autotune caches: engines serving structurally identical
# layers (same shapes/attrs) share measurements; the agnostic cache
# carries winners across batch buckets (autotune.py module docstring).
_AUTOTUNE_CACHE: dict = {}
_AUTOTUNE_AGNOSTIC: dict = {}


@dataclasses.dataclass
class PhoneBitEngine:
    spec: Sequence[Any]
    packed: list[dict]
    input_hw: tuple[int, int]
    matmul_mode: str = "xla"
    batch_size: int | None = None  # autotune/memory-plan batch (default 1)

    # ---- construction ----------------------------------------------------
    @classmethod
    def from_trained(cls, params, spec, input_hw, **kw) -> "PhoneBitEngine":
        """Offline conversion (Fig 2): fold + pack trained params."""
        packed = converter.convert(params, spec, input_hw)
        return cls(spec=spec, packed=packed, input_hw=input_hw, **kw)

    @classmethod
    def from_artifact(cls, path: str, spec, input_hw,
                      **kw) -> "PhoneBitEngine":
        return cls(spec=spec, packed=converter.load_artifact(path),
                   input_hw=input_hw, **kw)

    def save_artifact(self, path: str) -> None:
        converter.save_artifact(path, self.packed)

    # ---- artifact/metadata separation ------------------------------------
    def prepare(self) -> tuple[list[dict], list[dict]]:
        """Split the packed artifact into traced arrays vs static metadata.

        ``c_per_pos`` entries are static layout metadata (they become slice
        bounds inside jit), so they must leave the traced pytree.  This is
        an explicit, side-effect-free method — callable in any order
        relative to inference — returning ``(arrays, meta)``; both the
        legacy flat path and tooling use it instead of relying on jit
        construction order.
        """
        meta = [{k: int(v) for k, v in layer.items() if k == "c_per_pos"}
                for layer in self.packed]
        arrays = [{k: v for k, v in layer.items() if k != "c_per_pos"}
                  for layer in self.packed]
        return arrays, meta

    # ---- graph runtime path (default) ------------------------------------
    @functools.cached_property
    def _graph(self):
        from repro import runtime

        return runtime.fuse_pool_epilogue(
            runtime.lower_packed(self.spec, self.packed, self.input_hw))

    @functools.cached_property
    def _compiled(self) -> dict:
        """The per-bucket executable cache: (batch, donate, dp) → executor."""
        return {}

    @functools.cached_property
    def _tuner(self):
        """One Autotuner per engine: the disk cache is read once, not
        once per compiled bucket (winners still shared process-wide via
        the module caches)."""
        from repro import runtime

        return runtime.Autotuner(cache=_AUTOTUNE_CACHE,
                                 agnostic_cache=_AUTOTUNE_AGNOSTIC)

    def compile(self, batch_size: int | None = None, *,
                donate_input: bool = False, data_parallel: int = 1,
                mode: str | None = None, pipeline=None, mesh=None,
                data_axis: str = "data"):
        """Build (once) the executable for one serving bucket.

        Returns the cached :class:`GraphExecutor` for
        ``(batch_size, donate_input, data_parallel, mode)``, constructing
        and — under ``matmul_mode="auto"`` — autotuning it on first
        request.  Autotuning happens at the **per-device** shard shape
        (``batch_size // data_parallel``) so a data-parallel server reuses
        the winners of the equivalent single-device bucket, and winners
        transfer across buckets where the tile does not span the batch
        dim.  Serve-time calls at a compiled bucket never retrace.

        ``mode`` overrides ``matmul_mode`` for this executable only —
        the serving resilience layer (DESIGN.md §11.3) uses it to demote
        a failing bucket down the backend ladder without touching the
        engine's configured mode (all modes are bit-exact, so a demoted
        bucket serves identical results).

        ``pipeline`` is a sequence of devices for pipeline-parallel
        placement (DESIGN.md §13): the bucket compiles to a
        :class:`~repro.runtime.placement.StagedExecutor` — one
        executable per stage, cut at HBM touch points, params committed
        per device.  Mutually exclusive with ``data_parallel > 1``
        (compose data-parallel *replicas of pipelines* via
        :class:`~repro.distributed.replicas.ReplicaGroup` instead).

        ``data_parallel > 1`` runs the bucket per shard of the batch
        under ``shard_map`` over ``mesh[data_axis]`` (default: a 1-D mesh
        of the first ``data_parallel`` devices) — Pallas kernels are not
        partitioned automatically.
        """
        from repro import runtime

        mode = mode or self.matmul_mode
        bs = batch_size if batch_size is not None else (self.batch_size or 1)
        if bs < 1:
            raise ValueError(f"batch_size must be >= 1, got {bs}")
        if data_parallel > 1 and bs % data_parallel:
            raise ValueError(
                f"bucket {bs} not divisible by data_parallel={data_parallel}")
        if pipeline is not None and data_parallel > 1:
            raise ValueError("pipeline placement and data_parallel > 1 "
                             "are mutually exclusive on one executable; "
                             "compose replicas of pipelines instead")
        # The 4-tuple key is the artifact-compat surface
        # (artifact._install_executable); pipeline buckets extend it, so
        # the two key shapes can never collide.
        key = (bs, donate_input, data_parallel, mode)
        if pipeline is not None:
            key = key + (tuple(str(d) for d in pipeline),)
        if key not in self._compiled:
            if _faults._PLAN is not None:
                _faults.maybe_fault("engine.compile", bucket=bs, mode=mode)
            with _trace.span("compile.executor", "compile", bucket=bs,
                             mode=mode,
                             data_parallel=data_parallel):
                if pipeline is not None:
                    from repro.runtime import placement as _placement

                    exe = _placement.staged_executor(
                        self._graph, self._plan_shape(bs), tuple(pipeline),
                        mode=mode, donate_input=donate_input,
                        tuner=(self._tuner if mode == "auto"
                               or jax.default_backend() == "tpu"
                               else None))
                elif mode == "auto":
                    exe = self._tuner.tuned_executor(
                        self._graph,
                        self._plan_shape(max(bs // data_parallel, 1)),
                        donate_input=donate_input)
                elif mode == "vpu_chain":
                    # Region-fused serving (DESIGN.md §9): chains of packed
                    # ops run as single megakernel calls.  Per-chain tile
                    # shapes are autotuned on TPU only — interpret-mode
                    # timings are validators, not contenders (same policy as
                    # ``default_candidates``).
                    exe = runtime.chain_executor(
                        self._graph,
                        self._plan_shape(max(bs // data_parallel, 1)),
                        tuner=(self._tuner if jax.default_backend() == "tpu"
                               else None),
                        donate_input=donate_input)
                else:
                    exe = runtime.GraphExecutor(self._graph, mode,
                                                donate_input=donate_input)
                if data_parallel > 1:
                    if mesh is None:
                        mesh = jax.make_mesh(
                            (data_parallel,), (data_axis,),
                            devices=jax.devices()[:data_parallel])
                    exe = exe.sharded(mesh, data_axis)
            self._record_compile_metrics(exe, bs, data_parallel)
            self._compiled[key] = exe
        return self._compiled[key]

    def _record_compile_metrics(self, exe, bs: int,
                                data_parallel: int) -> None:
        """Publish runtime-wide memory series for a freshly built bucket:
        the arena plan's peak and, for region-fused executors, the HBM
        round-trip traffic the chains keep in VMEM (DESIGN.md §10.2)."""
        from repro import runtime

        reg = _obs_metrics.get_registry()
        plan = runtime.plan_memory(
            exe.graph, self._plan_shape(max(bs // data_parallel, 1)))
        reg.gauge("runtime.arena_peak_bytes").set(plan.peak_bytes())
        if getattr(exe, "regions", None):
            reg.gauge("runtime.chain_hbm_bytes_avoided").set(
                sum(c.hbm_bytes_avoided() for c in exe.regions))

    @property
    def _executor(self):
        """Default-bucket executor (``batch_size`` or 1) — introspection
        surface for ``memory_plan``/``backend_choices``."""
        return self.compile()

    @property
    def trace_count(self) -> int:
        """Total jit traces across every compiled bucket (serve-time
        no-recompile hook: this must stay flat while requests flow).
        AOT-loaded buckets contribute a constant 0 — they were never
        traced in this process."""
        return sum(e.trace_count for e in self._compiled.values())

    # ---- AOT executable artifacts (DESIGN.md §12) ------------------------
    def _install_executable(self, batch_size: int, exe, *,
                            donate_input: bool = False,
                            data_parallel: int = 1,
                            mode: str | None = None) -> None:
        """Register a prebuilt bucket executable under the same cache key
        :meth:`compile` would use — the artifact loader's entry point."""
        key = (int(batch_size), donate_input, data_parallel,
               mode or self.matmul_mode)
        self._compiled[key] = exe

    def export_artifact(self, path, buckets=(1, 2, 4, 8), *,
                        donate_input: bool = True) -> dict:
        """Serialize one AOT bucket executable per bucket (plus the
        autotune winner table and a provenance meta block) into the
        directory ``path`` — the offline half of zero-warmup serving.
        Distinct from :meth:`save_artifact`, which stores the packed
        *weights* (npz); this stores compiled *executables*."""
        from repro.serving import artifact as _artifact

        return _artifact.export_artifact(self, path, buckets,
                                         donate_input=donate_input)

    def load_artifact(self, path, *, donate_input: bool = True,
                      data_parallel: int = 1, buckets=None) -> dict:
        """Restore AOT bucket executables exported by
        :meth:`export_artifact` into the per-bucket cache with zero
        traces; per-bucket environment mismatches fall back to live
        compile (structured ``artifact.miss`` events), corrupt bytes
        raise :class:`~repro.serving.artifact.ArtifactError`."""
        from repro.serving import artifact as _artifact

        return _artifact.load_artifact(self, path,
                                       donate_input=donate_input,
                                       data_parallel=data_parallel,
                                       buckets=buckets)

    def _plan_shape(self, batch: int | None = None
                    ) -> tuple[int, int, int, int]:
        h, w = self.input_hw
        c = next((l.c_in for l in self.spec
                  if isinstance(l, (bnn_model.BConv, bnn_model.FloatConv))),
                 3)
        return (batch or self.batch_size or 1, h, w, c)

    def __call__(self, x_uint8: jnp.ndarray) -> jnp.ndarray:
        h, w = self.input_hw
        assert x_uint8.shape[1:3] == (h, w), (x_uint8.shape, self.input_hw)
        return self.compile(x_uint8.shape[0])(x_uint8)

    # ---- legacy flat path (cross-check oracle) ---------------------------
    @functools.cached_property
    def _jitted_flat(self):
        spec = self.spec
        _, meta = self.prepare()
        impl = "pm1" if self.matmul_mode in _PM1_MODES else "xor"

        @jax.jit
        def fwd(arrays, x):
            packed = [dict(a, **m) for a, m in zip(arrays, meta)]
            return bnn_model.packed_forward(packed, spec, x, impl=impl)

        return fwd

    def legacy_call(self, x_uint8: jnp.ndarray) -> jnp.ndarray:
        """The pre-graph flat ``packed_forward`` walk (oracle)."""
        arrays, _ = self.prepare()
        return self._jitted_flat(arrays, x_uint8)

    def cross_check(self, x_uint8: jnp.ndarray) -> jnp.ndarray:
        """Run the graph path and assert bit-exactness vs the flat path."""
        import numpy as np

        got = self(x_uint8)
        ref = self.legacy_call(x_uint8)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
        return got

    # ---- introspection ---------------------------------------------------
    def memory_plan(self):
        """Static arena plan for the serving graph (DESIGN.md §4.4)."""
        from repro import runtime

        return runtime.plan_memory(self._executor.graph, self._plan_shape())

    @property
    def backend_choices(self) -> list[dict]:
        """Per-node backend decisions (fixed mode or autotune winners)."""
        return self._executor.backend_report()

    @property
    def model_bytes(self) -> int:
        return converter.model_bytes(self.packed)
