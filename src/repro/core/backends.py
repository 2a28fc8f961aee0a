"""Pluggable execution backends for the CoDR engine.

The paper's accelerator is one fixed datapath; a software reproduction
grows several — the fused XLA tile dispatch, the faithful NumPy MPE/APE
execution model, the Pallas SMM kernel, the fused-decode matmul kernel.
Previously each was reachable through a different stringly-typed knob
(``CodrModel.run(backend=...)`` if/else chains, ``smm_forward(kernel=...)``).
This module makes backends first class:

* :class:`BackendCaps` — declarative capability flags (stride support,
  integer-activation requirement, which layer kinds execute natively).
  Kernel-adjacent facts live next to the kernels themselves
  (``repro.kernels.*.ops.KERNEL_CAPS``) and are consumed here.
* :class:`Backend` — the protocol: ``conv(layer, x)`` / ``linear(layer,
  x)`` steps plus ``run_model(model, x)`` chaining, with ``supports``
  answering *can this backend execute that layer, and if not, why not*.
* a **registry** — :func:`register` / :func:`get_backend` /
  :func:`available_backends` / :func:`resolve`.  ``repro.core.engine``
  and ``repro.core.api`` dispatch exclusively through it; the ROADMAP's
  multi-device sharding and async-serving work plug in here as new
  registered backends.

Built-ins registered at import:

``tiled``        fused ``lax.conv`` tile dispatch (any stride, float path)
``smm``          NumPy faithful MPE/APE execution (integer activations)
``smm_kernel``   Pallas MPE/APE kernel, batch in the grid (integer acts)
``codr_matmul``  Pallas fused decode+matmul (linear-only models)
``sharded``      shard_map tile-parallel executor over all local devices

Registering your own backend (worked example)::

    import jax, repro.api as codr

    class DenseDemoBackend(codr.Backend):
        '''Executes the decoded tile stack as one dense conv per layer
        — the minimal real backend.  The layer surface it relies on
        (``code`` / ``kind`` / ``stride`` / ``tiles_device`` plus the
        shared :meth:`Backend.finish` epilogue) is all any backend
        needs.'''

        name = "dense_demo"
        caps = codr.BackendCaps(max_stride=1,
                                description="toy dense executor")

        def conv(self, layer, x):
            t = layer.tiles_device                   # (T, t_m, N, RK, CK)
            w = t.reshape(-1, *t.shape[2:])[: layer.code.shape[0]]
            y = jax.lax.conv_general_dilated(
                x, w, window_strides=(1, 1), padding="VALID",
                dimension_numbers=("NHWC", "OIHW", "NHWC"))
            return self.finish(layer, y * layer.code.scale)

    codr.register(DenseDemoBackend())
    compiled = codr.compile(spec, cfg, backend="dense_demo")  # just works

``compile`` now capability-checks specs against it (stride 2 convs are
rejected at compile time with the reason, because of ``max_stride=1``),
and every surface accepting a backend name — ``CompiledModel.run``,
``CodrModel.run``, benchmarks — can select it.
"""
from __future__ import annotations

import abc
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from jax import shard_map as _shard_map
from jax.sharding import PartitionSpec as _P

from repro.core import smm, ucr
from repro.runtime import tracing

__all__ = [
    "Backend", "BackendCaps", "available_backends", "get_backend",
    "register", "resolve", "TiledBackend", "SmmBackend",
    "SmmKernelBackend", "CodrMatmulBackend", "ShardedBackend",
]


# ---------------------------------------------------------------------------
# capabilities
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class BackendCaps:
    """What a backend can execute, declaratively.

    ``max_stride``           ``None`` = any stride.
    ``integer_activations``  the backend runs the 8-bit feature datapath:
                             integer-valued inputs execute exactly,
                             anything else is int8-quantized first.
    ``native_kinds``         layer kinds the backend executes itself;
                             other kinds fall back per ``fallback_kinds``.
    ``fallback_kinds``       kinds delegated to the layer's own tiled
                             forward (empty = unsupported kinds error).
    ``packed_matmul``        the backend can execute a packed projection
                             leaf (:class:`repro.core.codr_linear.
                             PackedLinear`) via :meth:`Backend.matmul` —
                             the transformer serving lane
                             (``repro.api.compile_params`` gates on it).
    """

    max_stride: int | None = None
    integer_activations: bool = False
    native_kinds: frozenset = frozenset({"conv", "linear"})
    fallback_kinds: frozenset = frozenset()
    packed_matmul: bool = False
    description: str = ""

    def supports_stride(self, stride: int) -> bool:
        return self.max_stride is None or stride <= self.max_stride

    def supports_kind(self, kind: str) -> bool:
        return kind in self.native_kinds or kind in self.fallback_kinds


# ---------------------------------------------------------------------------
# backend protocol
# ---------------------------------------------------------------------------

def _finish(layer, y: jax.Array) -> jax.Array:
    """Shared epilogue: bias + activation (what every datapath appends
    after its accumulators drain)."""
    if layer.bias is not None:
        y = y + jnp.asarray(layer.bias)
    return jax.nn.relu(y) if layer.activation == "relu" else y


def _int_activations(x) -> tuple[np.ndarray, float]:
    """The accelerator's 8-bit feature path: integer-valued inputs within
    int8 range pass through exactly; anything else is symmetric
    int8-quantized (its scale folds into the output)."""
    xf = np.asarray(x, dtype=np.float32)
    if np.array_equal(xf, np.rint(xf)) and np.abs(xf).max() <= 127:
        return xf.astype(np.int32), 1.0
    q8, s = ucr.quantize_int8(xf)
    return q8.astype(np.int32), float(np.asarray(s))


class Backend(abc.ABC):
    """One way to execute CoDR layers.  Layers are duck-typed
    (:class:`repro.core.engine.CodrConv2D` / ``CodrLinear`` or anything
    exposing the same ``code`` / ``kind`` / ``stride`` surface).

    The contract, in full:

    * Subclasses MUST set a non-empty ``name`` (the registry key), a
      ``caps`` :class:`BackendCaps` describing what they execute, and
      implement :meth:`conv`.  :meth:`linear` defaults to the layer's
      own fused tiled matmul (declare ``"linear"`` in
      ``caps.fallback_kinds`` when relying on that).
    * Callers MUST gate on :meth:`supports` /
      :meth:`supports_model` before executing — ``compile`` and
      ``CompiledModel.run(backend=...)`` do, so an execution method may
      assume its layer passed the capability check and is free to fail
      arbitrarily (not just ``ValueError``) on layers that did not.
    * Numerics: every datapath must end with the shared
      :meth:`finish` epilogue (bias, then activation) in that op order —
      cross-backend parity tests depend on it.  Integer-activation
      backends (``caps.integer_activations``) additionally quantize
      non-integer inputs to int8 first; their outputs match the
      dequantized oracle only near-exactly, not bit-for-bit.
    """

    name: str = ""
    caps: BackendCaps = BackendCaps()

    # -- capability queries -------------------------------------------------
    def supports(self, layer) -> tuple[bool, str]:
        """``(ok, reason)`` — can this backend execute ``layer``?

        ``ok=False`` comes with a human-readable ``reason`` (the string
        ``compile`` raises with).  The default implementation checks
        ``caps``: the layer kind must be native or a declared fallback,
        and a conv layer's stride must not exceed ``caps.max_stride``.
        Override for capability rules the flags cannot express; never
        raise from here — report, don't throw.
        """
        if not self.caps.supports_kind(layer.kind):
            return False, (f"backend {self.name!r} has no {layer.kind!r} "
                           f"path (native: {sorted(self.caps.native_kinds)})")
        stride = getattr(layer, "stride", 1)
        if layer.kind == "conv" and not self.caps.supports_stride(stride):
            return False, (f"backend {self.name!r} supports stride <= "
                           f"{self.caps.max_stride}, layer {layer.name!r} "
                           f"has stride {stride}")
        return True, ""

    def supports_model(self, layers) -> tuple[bool, str]:
        """``(ok, reason)`` over a whole layer stack: the first failing
        layer's reason, or ``(True, "")`` when every layer passes."""
        for layer in layers:
            ok, reason = self.supports(layer)
            if not ok:
                return False, reason
        return True, ""

    # -- execution ----------------------------------------------------------
    @abc.abstractmethod
    def conv(self, layer, x: jax.Array) -> jax.Array:
        """Forward one conv layer from its code.

        ``x`` is NHWC ``(B, RI, CI, N)``; returns NHWC
        ``(B, RO, CO, M)`` float32 with VALID padding and the layer's
        stride, scale, bias, and activation applied (end with
        :meth:`finish`).  May assume :meth:`supports` passed."""

    def linear(self, layer, x: jax.Array) -> jax.Array:
        """Forward one linear layer, ``(B, N)`` → ``(B, M)`` float32,
        scale/bias/activation applied.  Default: delegate to the layer's
        own fused tiled matmul (the ``fallback_kinds`` path)."""
        return layer(x)

    def step(self, layer, x: jax.Array) -> jax.Array:
        """Dispatch one layer by ``layer.kind``.  Raises ``ValueError``
        on kinds that are neither ``"conv"`` nor ``"linear"`` — kinds
        the capability check already rejects for built-ins."""
        if layer.kind == "conv":
            return self.conv(layer, x)
        if layer.kind == "linear":
            return self.linear(layer, x)
        raise ValueError(f"unknown layer kind {layer.kind!r}")

    def finish(self, layer, y: jax.Array) -> jax.Array:
        """The shared epilogue every datapath appends after its
        accumulators drain: ``+ bias`` (if any), then the activation.
        Public so custom backends reproduce the exact op order —
        bit-for-bit parity across backends depends on it."""
        return _finish(layer, y)

    def matmul(self, x: jax.Array, w) -> jax.Array:
        """Execute one packed projection leaf
        (:class:`repro.core.codr_linear.PackedLinear`):
        ``(..., K) @ dequantize(w) → (..., out_features)`` in ``x``'s
        dtype.  This is the transformer serving entry point —
        ``models.common.linear`` routes packed params leaves here.

        The default is decode-then-matmul with *exactly* the dense
        ``linear`` numerics (dequantized f32 weight cast to ``x.dtype``,
        then ``jnp.dot``), so a backend relying on it — ``tiled``,
        ``sharded`` — produces logits bit-for-bit equal to serving the
        quantize-applied dense params.  Kernel backends override with a
        fused datapath (``codr_matmul`` decodes in VMEM inside the MXU
        tiles, f32 accumulation — near-exact, not bit-for-bit).  Only
        meaningful when ``caps.packed_matmul`` is set; ``compile_params``
        gates on that flag."""
        return jnp.dot(x, w.dense().astype(x.dtype))

    def grouped_matmul(self, x: jax.Array, group_sizes: jax.Array, w, *,
                       max_group: int | None = None) -> jax.Array:
        """Execute one packed expert stack (a ``PackedLinear`` whose pack
        leads with the expert dim ``E``): rows of ``x (m, K)`` sorted by
        expert, ``group_sizes[e]`` of them for expert ``e``, each times
        its expert's dequantized matrix → ``(m, out_features)`` in
        ``x``'s dtype.  ``models.common.grouped_linear`` routes MoE
        expert stacks here; ``max_group`` bounds any one group.

        The default decodes the whole stack and runs ``lax.ragged_dot``
        with the dense ``grouped_linear`` numerics — the reference lane
        (``tiled``, ``sharded``).  ``codr_matmul`` overrides it with the
        grouped kernel, which decodes only the experts given rows."""
        del max_group
        return jax.lax.ragged_dot(x, w.dense().astype(x.dtype), group_sizes)

    def gather(self, tokens: jax.Array, w) -> jax.Array:
        """Embedding lookup on a packed vocabulary table
        (:class:`repro.core.codr_linear.PackedEmbedding`): gather the
        packed rows for ``tokens`` and decode only those.  The default
        row-gather decode is bit-for-bit equal to indexing the
        quantize-applied dense table, so every backend inherits exact
        parity with the dense reference lane; ``models.common.
        embedding_lookup`` routes packed embed leaves here."""
        return w.lookup(tokens)

    def unembed(self, x: jax.Array, w) -> jax.Array:
        """Logit projection ``x @ dense(w).T`` against a packed output
        embedding — decode-then-matmul with the dense ``unembed``
        numerics (dequantized f32 table cast to ``x.dtype``), bit-equal
        to serving the quantize-applied dense table."""
        return jnp.dot(x, w.dense().T.astype(x.dtype))

    def run_model(self, model, batch: jax.Array) -> jax.Array:
        """Forward a batch through a :class:`~repro.core.engine.CodrModel`
        (or any object exposing ``_chain``): casts to float32, chains
        :meth:`step` over the layers, auto-flattening at the
        conv→linear boundary.  Override to add whole-model structure
        (the ``tiled``/``sharded`` backends jit the entire chain once
        and cache it on the model)."""
        return model._chain(jnp.asarray(batch, jnp.float32), self.step)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

_REGISTRY: dict[str, Backend] = {}


def register(backend: Backend, *, overwrite: bool = False) -> Backend:
    """Add a backend instance to the registry (name taken from the
    instance).  Future executors — sharded, async, TPU-tuned — register
    here and become selectable everywhere a backend name is accepted."""
    if not backend.name:
        raise ValueError("backend must set a non-empty .name")
    if backend.name in _REGISTRY and not overwrite:
        raise ValueError(f"backend {backend.name!r} already registered "
                         f"(pass overwrite=True to replace)")
    _REGISTRY[backend.name] = backend
    return backend


def available_backends() -> tuple[str, ...]:
    """Registered backend names, registration order."""
    return tuple(_REGISTRY)


def get_backend(name: str) -> Backend:
    """Look up a registered backend by name.  Raises ``ValueError``
    naming the registered alternatives on a miss — the same error
    surface ``compile(..., backend="typo")`` shows."""
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(f"unknown backend {name!r}; registered: "
                         f"{', '.join(_REGISTRY) or '(none)'}") from None


def resolve(backend: str | Backend) -> Backend:
    """Accept a registered name or a Backend instance."""
    if isinstance(backend, Backend):
        return backend
    return get_backend(backend)


# ---------------------------------------------------------------------------
# built-in backends
# ---------------------------------------------------------------------------

class TiledBackend(Backend):
    """Fused XLA tile dispatch (default): each layer's decoded tile stack
    collapses into ONE ``lax.conv`` / matmul per layer, the whole model
    chain jitted once per input shape (compile-once contract)."""

    name = "tiled"
    caps = BackendCaps(packed_matmul=True,
                       description="fused lax.conv/matmul tile dispatch, "
                                   "any stride, float datapath")

    def conv(self, layer, x):
        return layer(x)

    def run_model(self, model, batch):
        # whole-model jitted chain, cached on the model — XLA fuses across
        # layer boundaries; repeat same-shape requests re-trace nothing
        if model._run_tiled is None:
            def cnn_chain(x):
                return model._chain(x, lambda l, xx: l(xx))
            model._run_tiled = jax.jit(cnn_chain)
        with tracing.span("model.put", batch=len(batch)):
            x = jnp.asarray(batch, jnp.float32)
            if tracing.enabled():
                # traced: the span ends when the input has landed on the
                # device, so it covers the device's wait for the transfer
                x.block_until_ready()
        with tracing.span("model.dispatch"):
            return model._run_tiled(x)


class SmmBackend(Backend):
    """Faithful MPE/APE execution model in NumPy
    (:func:`repro.core.smm.conv2d_smm_batched`): differential
    scalar–matrix multiplies + crossbar routing, bit-exact in int32,
    broadcasting every routed window over the batch axis."""

    name = "smm"
    caps = BackendCaps(integer_activations=True,
                       native_kinds=frozenset({"conv"}),
                       fallback_kinds=frozenset({"linear"}),
                       description="NumPy faithful MPE/APE execution "
                                   "(8-bit feature path)")

    def conv(self, layer, x):
        xi, x_scale = _int_activations(x)
        scale = float(np.asarray(layer.code.scale)) * x_scale
        outs = smm.conv2d_smm_batched(np.moveaxis(xi, 3, 1), layer.code,
                                      layer.stride)
        return _finish(layer, jnp.asarray(np.moveaxis(outs, 1, 3),
                                          jnp.float32) * scale)


class SmmKernelBackend(Backend):
    """Pallas MPE/APE kernel (:mod:`repro.kernels.smm_conv`): the whole
    batch in one dispatch via a batch grid dimension, operands packed
    once per layer and cached on it."""

    name = "smm_kernel"
    _caps: BackendCaps | None = None

    def supports(self, layer) -> tuple[bool, str]:
        if jax.default_backend() == "tpu":
            from repro.kernels.smm_conv import ops as smm_ops
            return False, (f"backend {self.name!r} does not run on a TPU: "
                           f"{smm_ops.TPU_REFUSAL}")
        return super().supports(layer)

    @property
    def caps(self) -> BackendCaps:
        # resolved lazily from the kernel's own KERNEL_CAPS so merely
        # importing repro.core never pulls in jax.experimental.pallas
        if self._caps is None:
            from repro.kernels.smm_conv import ops as smm_ops
            kc = smm_ops.KERNEL_CAPS
            self._caps = BackendCaps(
                integer_activations=kc["integer_activations"],
                max_stride=kc["max_stride"],
                native_kinds=frozenset(kc["kinds"]),
                # linear layers fall back to the fused tiled matmul — a
                # backend policy, not a kernel fact
                fallback_kinds=frozenset({"linear"}),
                description=kc["description"])
        return self._caps

    def conv(self, layer, x):
        from repro.kernels.smm_conv import smm_conv_batched
        xi, x_scale = _int_activations(x)
        scale = float(np.asarray(layer.code.scale)) * x_scale
        y = smm_conv_batched(jnp.asarray(np.moveaxis(xi, 3, 1), jnp.float32),
                             layer.code, stride=layer.stride,
                             operands=layer.smm_operands())
        return _finish(layer, jnp.moveaxis(y, 1, 3) * scale)


class CodrMatmulBackend(Backend):
    """Pallas fused decode+matmul (:mod:`repro.kernels.codr_matmul`):
    linear layers execute from the fixed-width unique-index pack, the
    table gather fused into the MXU tiles.  Linear-only — a model with
    conv layers is rejected at compile time via :meth:`supports`."""

    name = "codr_matmul"
    _caps: BackendCaps | None = None

    @property
    def caps(self) -> BackendCaps:
        if self._caps is None:
            from repro.kernels.codr_matmul import ops as mm_ops
            kc = mm_ops.KERNEL_CAPS
            self._caps = BackendCaps(
                native_kinds=frozenset(kc["kinds"]),
                integer_activations=kc["integer_activations"],
                packed_matmul=kc.get("packed_matmul", False),
                description=kc["description"])
        return self._caps

    def conv(self, layer, x):                      # pragma: no cover
        raise NotImplementedError("codr_matmul is linear-only")

    def matmul(self, x, w):
        """Fused decode+matmul from the packed bitstream: the table
        gather happens in VMEM inside the MXU tiles (interpret mode on
        CPU).  f32 accumulation — matches the dense reference to float
        tolerance, tighter than the bf16 dot it replaces."""
        from repro.kernels.codr_matmul import codr_matmul
        if w.weight.packed.ndim != 2:
            raise ValueError(
                "codr_matmul executes per-matrix packed operands; got a "
                f"stacked pack of shape {w.weight.packed.shape} — slice "
                "the stack axis (lax.scan does) or decode via "
                "dense_weight() first")
        lead = x.shape[:-1]
        x2 = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
        y = codr_matmul(x2, w.weight)[:, : w.out_features]
        return y.reshape(*lead, w.out_features).astype(x.dtype)

    def grouped_matmul(self, x, group_sizes, w, *, max_group=None):
        """The grouped kernel (:mod:`repro.kernels.codr_gmm`): each
        packed block of an expert that has rows is decoded in VMEM once
        per call, an expert without rows is skipped."""
        from repro.kernels.codr_gmm import codr_gmm
        if w.weight.packed.ndim != 3:
            raise ValueError(
                "grouped_matmul executes one (E, K, N) expert stack; got "
                f"a pack of shape {w.weight.packed.shape}")
        y = codr_gmm(x.astype(jnp.float32), group_sizes, w.weight,
                     max_group=max_group)[:, : w.out_features]
        return y.astype(x.dtype)

    def linear(self, layer, x):
        from repro.core.codr_linear import pack_unique
        from repro.kernels.codr_matmul import codr_matmul
        packed = getattr(layer, "_mm_packed", None)
        if packed is None:
            # decoded (M, N) int8 → (K=N_in, N=M_out) pack; pad M_out to
            # a multiple of 32 — every per-word width pack_unique may
            # choose divides 32, so the pack always lines up whatever
            # bit-length the (possibly pad-grown) unique table needs —
            # and crop the extra columns after the matmul
            q = layer.decoded_weights().T            # (N_in, M_out) int8
            pad = (-q.shape[1]) % 32
            if pad:
                q = np.pad(q, ((0, 0), (0, pad)))
            packed = pack_unique(q, float(np.asarray(layer.code.scale)),
                                 dtype=jnp.float32)
            layer._mm_packed = packed
        m = layer.code.shape[0]
        y = codr_matmul(jnp.asarray(x, jnp.float32), packed)[:, :m]
        return _finish(layer, y)


class ShardedBackend(Backend):
    """Tile-parallel scale-out executor: each layer's decoded tile stack
    is partitioned across devices over the **output-tile axis** — the
    CoDR loop nest's natural model-parallel dimension, since every
    output-channel tile's results are produced exactly once (output
    stationary) while the input is broadcast to all tiles (semi input
    stationary, paper §III-B).  Mapping that dataflow onto a mesh:

    * the tile stack ``(n_tiles, t_m, N, RK, CK)`` is zero-padded to a
      multiple of the device count and ``jax.device_put`` once, sharded
      over its leading axis (:func:`repro.sharding.rules.shard_leading`);
    * the forward is a ``shard_map`` over the 1-D ``tile`` mesh
      (:func:`repro.sharding.rules.tile_mesh`): every device runs ONE
      ``lax.conv`` / matmul on its local tile slice with the batch
      replicated, and the output concatenates over the channel axis with
      no cross-device collective in the hot loop;
    * pad channels are cropped and the scale/bias/activation epilogue is
      applied on the gathered output — elementwise, so results are
      **bit-for-bit identical** to the ``tiled`` backend's fused
      single-device dispatch (per-output-channel reductions are
      independent of the channel split).

    On a single device the 1-element mesh makes ``shard_map`` the
    identity partitioning — the fallback that keeps 1-device CI green —
    and the same code scales to any local device count, including a
    forced host-platform mesh
    (``XLA_FLAGS=--xla_force_host_platform_device_count=N``).

    Constructor args:
        ``mesh``: a 1-D :class:`jax.sharding.Mesh` whose only axis is
        the tile axis; ``None`` (default) builds one over all local
        devices on first use.  Pass an explicit mesh to pin the executor
        to a device subset: ``register(ShardedBackend(mesh, name="..."))``.
    """

    name = "sharded"
    caps = BackendCaps(packed_matmul=True,
                       description="shard_map tile-parallel dispatch over "
                                   "the output-tile axis, any stride, "
                                   "float datapath, 1-device fallback")

    # fault-injection hook (class attr: zero cost until installed; see
    # repro.runtime.resilience — site "sharded.dispatch")
    _injector = None

    def __init__(self, mesh=None, *, name: str | None = None):
        self._mesh = mesh
        if name is not None:
            self.name = name

    def set_fault_injector(self, injector) -> "ShardedBackend":
        """Install (or clear, with ``None``) a ``FaultInjector`` firing
        the ``"sharded.dispatch"`` site on every whole-model dispatch —
        the hook chaos runs use to simulate a lost mesh device."""
        self._injector = injector
        return self

    @property
    def mesh(self):
        if self._mesh is None:
            from repro.sharding import rules
            self._mesh = rules.tile_mesh()
        return self._mesh

    @property
    def n_devices(self) -> int:
        from repro.sharding import rules
        return self.mesh.shape[rules.ENGINE_TILE_AXIS]

    # -- per-layer preparation ---------------------------------------------
    def _prepare(self, layer):
        """Shard ``layer``'s decoded tiles over the mesh (once per layer
        per mesh) and build the jitted shard_map forward.  Cached on the
        layer — repeat dispatches reuse the committed device buffers."""
        state = getattr(layer, "_shard_state", None)
        # Mesh defines value equality: an equal-but-distinct mesh (two
        # backends built over the same devices) still hits the cache
        if state is not None and state[0] == self.mesh:
            return state
        from repro.sharding import rules
        axis = rules.ENGINE_TILE_AXIS
        mesh = self.mesh
        t = layer.tiles.astype(np.float32)    # (n_tiles, t_m, N[, RK, CK])
        if layer.kind == "linear":
            t = t.reshape(t.shape[0], t.shape[1], -1)
        w_sh = rules.shard_leading(t, mesh, axis=axis)
        scale = float(np.asarray(layer.code.scale))
        m = layer.code.shape[0]

        if layer.kind == "conv":
            stride = (layer.stride, layer.stride)

            def local(x, tiles):
                # local slice (n_tiles/D, t_m, N, RK, CK) → one conv per
                # device; out_spec concatenates over the channel axis
                w = tiles.reshape(tiles.shape[0] * tiles.shape[1],
                                  *tiles.shape[2:])
                return jax.lax.conv_general_dilated(
                    x, w, window_strides=stride, padding="VALID",
                    dimension_numbers=("NHWC", "OIHW", "NHWC"))

            sm = _shard_map(local, mesh=mesh, in_specs=(_P(), _P(axis)),
                            out_specs=_P(None, None, None, axis))

            def fwd(x, w_sharded):
                return _finish(layer, sm(x, w_sharded)[..., :m] * scale)
        else:

            def local(x, tiles):
                w = tiles.reshape(tiles.shape[0] * tiles.shape[1], -1)
                return x @ w.T

            sm = _shard_map(local, mesh=mesh, in_specs=(_P(), _P(axis)),
                            out_specs=_P(None, axis))

            def fwd(x, w_sharded):
                return _finish(layer, sm(x, w_sharded)[:, :m] * scale)

        state = (mesh, w_sh, jax.jit(fwd))
        layer._shard_state = state
        return state

    def placement(self, layer) -> dict[int, tuple[int, ...]]:
        """Device id → shape of the slice of ``layer``'s tile stack that
        device holds (preparing the layer first)."""
        _, w_sh, _ = self._prepare(layer)
        return {s.device.id: tuple(s.data.shape)
                for s in w_sh.addressable_shards}

    # -- execution ----------------------------------------------------------
    def conv(self, layer, x):
        _, w_sh, fwd = self._prepare(layer)
        return fwd(jnp.asarray(x, jnp.float32), w_sh)

    linear = conv

    def run_model(self, model, batch):
        # whole-model jitted chain (compile-once, like TiledBackend) —
        # per-layer shard_maps inline into one computation, the sharded
        # tile buffers staying device-resident across requests
        if self._injector is not None:
            self._injector.fire("sharded.dispatch")
        state = getattr(model, "_run_sharded", None)
        if state is None or state[0] != self.mesh:
            for layer in model.layers:
                self._prepare(layer)
            fn = jax.jit(lambda x: model._chain(x, self.step))
            model._run_sharded = state = (self.mesh, fn)
        return state[1](jnp.asarray(batch, jnp.float32))


register(TiledBackend())
register(SmmBackend())
register(SmmKernelBackend())
register(CodrMatmulBackend())
register(ShardedBackend())
