"""The Workload abstraction: preprocess + model + postprocess as one object.

A :class:`Workload` bundles everything between an arbitrary-size uint8
image and a human-readable prediction (DESIGN.md §8):

* the task's preprocessing transform (letterbox for detection,
  center-crop for classification) — jit-able, and exposed as an
  ``InferenceServer`` ``preprocess=`` hook;
* the paper network (spec + a **seeded checkpoint** so every consumer —
  tests, benchmarks, examples — reconstructs bit-identical parameters
  from ``(name, seed)`` alone), served through the graph runtime via
  :class:`~repro.serving.engine.PhoneBitEngine`;
* the jit-able postprocess head (top-k / YOLO decode + fixed-size NMS),
  fused behind the engine's per-bucket executable surface by
  :class:`WorkloadEngine` so the server scatters *decoded* rows and the
  zero-serve-time-retrace contract covers the head too.

The registry maps workload names to builders::

    wl = workloads.get("yolov2_tiny_voc", input_hw=416)
    server = wl.server(max_batch=4)
    server.submit(any_uint8_image); server.drain()

Each paper entry also has a ``variant="tiny"`` — a topology-preserving
scaled-down network (same layer-type sequence: bit-plane first conv,
packed hidden stack, float head; reduced channels/resolution) used by the
conformance harness and CI, where sweeping interpret-mode Pallas backends
over full ImageNet-size nets is not viable.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.bnn_model import BConv, BDense, FloatConv, FloatDense, Pool
from repro.models import paper_nets
from repro.serving import InferenceServer, PhoneBitEngine
from repro.workloads import postprocess as post
from repro.workloads import preprocess as pre
from repro.workloads.postprocess import DetectConfig


def checkpoint_params(spec, seed: int = 0) -> list[dict]:
    """The seeded golden checkpoint: deterministic latent-float params.

    Drawn from one numpy generator seeded by ``seed`` — the distributions
    ``bnn_model.init_params`` uses (uniform(-1, 1) binary latents, scaled
    normal float layers), but independent of JAX's PRNG implementation
    and its defaults, so the checkpoint (and every golden fixture built
    on it) is the same under any JAX version.  A layer type added to
    ``init_params`` needs its draw here too.  BN statistics are
    randomized too: identity BN would make half the integer thresholds
    degenerate, and the golden fixtures and conformance sweeps need to
    exercise the threshold math.
    """
    rng = np.random.default_rng(seed)

    def arr(x):
        return jnp.asarray(x, jnp.float32)

    params: list[dict] = []
    for layer in spec:
        if isinstance(layer, (BConv, BDense)):
            shape = ((layer.kernel, layer.kernel, layer.c_in, layer.c_out)
                     if isinstance(layer, BConv)
                     else (layer.d_in, layer.d_out))
            o = shape[-1]
            params.append(dict(
                w=arr(rng.uniform(-1.0, 1.0, shape)),
                gamma=arr(rng.uniform(-1.5, 1.5, o)),
                beta=arr(rng.uniform(-1, 1, o)),
                mu=arr(rng.uniform(-20, 20, o)),
                var=arr(rng.uniform(0.5, 4, o))))
        elif isinstance(layer, (FloatConv, FloatDense)):
            shape = ((layer.kernel, layer.kernel, layer.c_in, layer.c_out)
                     if isinstance(layer, FloatConv)
                     else (layer.d_in, layer.d_out))
            fan = int(np.prod(shape[:-1]))
            params.append(dict(
                w=arr(rng.standard_normal(shape) / np.sqrt(fan)),
                b=arr(np.zeros(shape[-1]))))
        else:
            params.append({})
    return params


class WorkloadEngine:
    """A PhoneBitEngine with the workload's postprocess head fused onto
    its per-bucket executable surface.

    Speaks the same ``compile(bs, donate_input=, data_parallel=, mode=)`` /
    ``_plan_shape`` / ``trace_count`` contract the ``InferenceServer``
    expects from an engine, so the server serves decoded predictions with
    no special casing.  The head is one jit-compiled function (traced once
    per bucket shape; traces counted like the executor's), dispatched
    after the forward executable — composition at the host level keeps
    the engine's input-buffer donation intact.
    """

    def __init__(self, engine: PhoneBitEngine,
                 head: Callable[[jnp.ndarray], jnp.ndarray]):
        self.engine = engine
        self.head = head
        self._head_trace_count = 0

        def traced_head(y):
            self._head_trace_count += 1   # trace time only
            return head(y)

        self._head_jit = jax.jit(traced_head)
        self._compiled: dict[tuple, Callable] = {}

    # ---- engine surface (what InferenceServer consumes) ------------------
    def compile(self, batch_size: int | None = None, *,
                donate_input: bool = False, data_parallel: int = 1,
                mode: str | None = None, **kw):
        # Resolved-mode key (like PhoneBitEngine's): the server's health
        # ladder passes the concrete mode string, direct calls pass None
        # — both must hit the same cached (or artifact-loaded) entry.
        key = (batch_size, donate_input, data_parallel,
               mode or self.matmul_mode)
        if key not in self._compiled:
            fwd = self.engine.compile(batch_size, donate_input=donate_input,
                                      data_parallel=data_parallel, mode=mode,
                                      **kw)
            self._compiled[key] = \
                lambda x, fwd=fwd: self._head_jit(fwd(x))
        return self._compiled[key]

    def _plan_shape(self, batch: int | None = None):
        return self.engine._plan_shape(batch)

    @property
    def matmul_mode(self) -> str:
        """Configured backend rung — lets the server's degradation ladder
        (DESIGN.md §11.3) judge and demote workload engines too."""
        return self.engine.matmul_mode

    # ---- AOT artifacts (DESIGN.md §12) -----------------------------------
    # The artifact loader's engine surface: graph/tuner come from the
    # wrapped engine; loaded executables (forward + head composed) land
    # in THIS cache so the server's compile() hits them.
    @property
    def _graph(self):
        return self.engine._graph

    @property
    def _tuner(self):
        return self.engine._tuner

    def _install_executable(self, batch_size: int, exe, *,
                            donate_input: bool = False,
                            data_parallel: int = 1,
                            mode: str | None = None) -> None:
        key = (int(batch_size), donate_input, data_parallel,
               mode or self.matmul_mode)
        self._compiled[key] = exe

    def export_artifact(self, path, buckets=(1, 2, 4, 8), *,
                        donate_input: bool = True,
                        workload: str | None = None) -> dict:
        """Export AOT bucket executables *including the postprocess
        head* (serialized per bucket at the forward output shape), so a
        loaded workload serves decoded predictions with zero traces."""
        from repro.serving import artifact as _artifact

        return _artifact.export_artifact(
            self.engine, path, buckets, donate_input=donate_input,
            head_fn=self._head_jit, workload=workload)

    def load_artifact(self, path, *, donate_input: bool = True,
                      data_parallel: int = 1, buckets=None) -> dict:
        """Restore forward+head executables into this engine's bucket
        cache (``trace_count`` stays 0 — neither the executor closure
        nor the head jit is ever traced)."""
        from repro.serving import artifact as _artifact

        return _artifact.load_artifact(
            self, path, donate_input=donate_input,
            data_parallel=data_parallel, buckets=buckets, head=True)

    @property
    def trace_count(self) -> int:
        """Forward + head traces: the serve-time no-recompile contract
        covers the whole image->prediction executable."""
        return self.engine.trace_count + self._head_trace_count

    # ---- direct calls ----------------------------------------------------
    def __call__(self, x_uint8: jnp.ndarray) -> jnp.ndarray:
        return self.compile(x_uint8.shape[0])(x_uint8)

    def raw(self, x_uint8: jnp.ndarray) -> jnp.ndarray:
        """Pre-head network output (logits / feature map)."""
        return self.engine(x_uint8)

    def cross_check(self, x_uint8: jnp.ndarray) -> jnp.ndarray:
        """Decoded predictions via the engine's graph path, asserting the
        graph == legacy-flat bit-exactness on the raw output first."""
        return self._head_jit(self.engine.cross_check(x_uint8))


@dataclasses.dataclass
class Workload:
    """One deployable paper workload: preprocess -> engine -> postprocess."""

    name: str
    task: str                                  # "classify" | "detect"
    spec: list
    input_hw: tuple[int, int]
    params: list
    matmul_mode: str = "xla"
    top_k: int = 5
    detect: DetectConfig | None = None
    class_names: tuple[str, ...] | None = None
    seed: int = 0

    def __post_init__(self):
        assert self.task in ("classify", "detect"), self.task
        if self.task == "detect" and self.detect is None:
            self.detect = DetectConfig()

    # ---- preprocessing ---------------------------------------------------
    def preprocess(self, img: jnp.ndarray) -> jnp.ndarray:
        """(H, W, C) uint8 at any size -> network-size uint8 (jit-able)."""
        if self.task == "detect":
            return pre.letterbox(img, self.input_hw)
        return pre.center_crop_resize(img, self.input_hw)

    @functools.cached_property
    def preprocess_hook(self) -> Callable[[np.ndarray], np.ndarray]:
        """Numpy-in/out per-payload hook for ``InferenceServer``."""
        return pre.as_server_hook(self.preprocess)

    # ---- postprocessing --------------------------------------------------
    def postprocess(self, raw: jnp.ndarray) -> jnp.ndarray:
        """Network output -> fixed-size prediction rows (jit-able)."""
        if self.task == "detect":
            return post.detect_head(raw, self.detect, self.input_hw)
        return post.topk_head(raw, self.top_k)

    # ---- engine / serving ------------------------------------------------
    @functools.cached_property
    def engine(self) -> WorkloadEngine:
        base = PhoneBitEngine.from_trained(self.params, self.spec,
                                           self.input_hw,
                                           matmul_mode=self.matmul_mode)
        return WorkloadEngine(base, self.postprocess)

    def server(self, **kw) -> InferenceServer:
        kw.setdefault("preprocess", self.preprocess_hook)
        return InferenceServer(self.engine, **kw)

    def predict(self, images) -> np.ndarray:
        """End-to-end convenience: list of raw uint8 HWC images (any
        sizes) -> stacked prediction rows."""
        x = jnp.stack([self.preprocess(jnp.asarray(i)) for i in images])
        return np.asarray(self.engine(x))

    def format(self, row) -> list[dict]:
        """One request's prediction rows -> readable dicts."""
        if self.task == "detect":
            return post.detections_to_dicts(row, self.detect)
        return [dict(class_id=int(c), prob=float(p),
                     label=(self.class_names[int(c)]
                            if self.class_names else str(int(c))))
                for c, p in np.asarray(row)]

    @property
    def model_bytes(self) -> int:
        return self.engine.engine.model_bytes


# --------------------------------------------------------------------------
# Tiny (topology-preserving) conformance variants
# --------------------------------------------------------------------------

def _tiny_alexnet():
    """AlexNet shrunk for the conformance sweep: strided first bit-plane
    conv, packed conv/pool stack, two packed dense, float head."""
    spec = [
        BConv(3, 32, kernel=5, stride=2, pad=2, first=True),
        Pool(2, 2),
        BConv(32, 48, kernel=3, stride=1, pad=1),
        Pool(2, 2),
        BDense(2 * 2 * 48, 64),
        BDense(64, 64),
        FloatDense(64, 10),
    ]
    return spec, (16, 16)


def _tiny_vgg16():
    """VGG16 shrunk: doubled conv blocks between pools, dense tail."""
    spec = [
        BConv(3, 16, kernel=3, stride=1, pad=1, first=True),
        BConv(16, 16, kernel=3, stride=1, pad=1),
        Pool(2, 2),
        BConv(16, 32, kernel=3, stride=1, pad=1),
        BConv(32, 32, kernel=3, stride=1, pad=1),
        Pool(2, 2),
        BDense(4 * 4 * 32, 64),
        BDense(64, 64),
        FloatDense(64, 10),
    ]
    return spec, (16, 16)


def _tiny_yolov2(detect: DetectConfig):
    """YOLOv2-Tiny shrunk: conv/pool ladder ending in the darknet
    stride-1 'same' pool and the full-precision 1x1 detection head."""
    spec = [
        BConv(3, 16, kernel=3, stride=1, pad=1, first=True),
        Pool(2, 2),
        BConv(16, 32, kernel=3, stride=1, pad=1),
        Pool(2, 2),
        BConv(32, 64, kernel=3, stride=1, pad=1),
        Pool(2, 1, pad=(0, 1)),
        BConv(64, 64, kernel=3, stride=1, pad=1),
        FloatConv(64, detect.channels, kernel=1, stride=1, pad=0),
    ]
    return spec, (32, 32)


# --------------------------------------------------------------------------
# Registry
# --------------------------------------------------------------------------

_REGISTRY: dict[str, Callable[..., Workload]] = {}


def register(name: str, builder: Callable[..., Workload]) -> None:
    _REGISTRY[name] = builder


def names() -> list[str]:
    return sorted(_REGISTRY)


def get(name: str, **kw) -> Workload:
    """Build a registered workload.  Common kwargs: ``variant`` ("paper"
    default, or "tiny" for the conformance-scale net), ``matmul_mode``,
    ``input_hw`` (int or (h, w); fully-conv nets only), ``seed``."""
    if name not in _REGISTRY:
        raise KeyError(f"unknown workload {name!r}; have {names()}")
    return _REGISTRY[name](**kw)


def _hw(input_hw) -> tuple[int, int] | None:
    if input_hw is None:
        return None
    if isinstance(input_hw, int):
        return (input_hw, input_hw)
    return tuple(input_hw)


def _classify_builder(net: str, tiny_fn):
    def build(*, variant: str = "paper", matmul_mode: str = "xla",
              seed: int = 0, top_k: int = 5, input_hw=None) -> Workload:
        if variant == "paper":
            spec, (h, w, _) = paper_nets.get(net)
        elif variant == "tiny":
            spec, (h, w) = tiny_fn()
        else:
            raise ValueError(f"unknown variant {variant!r}")
        if _hw(input_hw) not in (None, (h, w)):
            raise ValueError(
                f"{net} has dense layers fixed to {(h, w)} inputs")
        return Workload(
            name=f"{net}_imagenet" if variant == "paper" else
                 f"{net}_imagenet[tiny]",
            task="classify", spec=spec, input_hw=(h, w),
            params=checkpoint_params(spec, seed),
            matmul_mode=matmul_mode, top_k=top_k, seed=seed)
    return build


def _detect_builder(name: str, net: str, tiny_fn):
    def build(*, variant: str = "paper", matmul_mode: str = "xla",
              seed: int = 0, input_hw=None,
              detect: DetectConfig | None = None) -> Workload:
        detect = detect if detect is not None else DetectConfig()
        if variant == "paper":
            spec, (h, w, _) = paper_nets.get(net)
        elif variant == "tiny":
            spec, (h, w) = tiny_fn(detect)
        else:
            raise ValueError(f"unknown variant {variant!r}")
        # Fully convolutional: any resolution the pool ladder divides.
        h, w = _hw(input_hw) or (h, w)
        return Workload(
            name=name if variant == "paper" else f"{name}[tiny]",
            task="detect", spec=spec, input_hw=(h, w),
            params=checkpoint_params(spec, seed),
            matmul_mode=matmul_mode, detect=detect,
            class_names=detect.class_names, seed=seed)
    return build


register("alexnet_imagenet", _classify_builder("alexnet", _tiny_alexnet))
register("vgg16_imagenet", _classify_builder("vgg16", _tiny_vgg16))
register("yolov2_tiny_voc",
         _detect_builder("yolov2_tiny_voc", "yolov2-tiny", _tiny_yolov2))
