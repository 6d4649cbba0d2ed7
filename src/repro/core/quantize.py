"""int8 quantization (paper C5): PTQ + QAT fake-quant, Jacob et al. 2017.

Weights: per-output-channel symmetric int8 (the last axis is treated as
the output-channel axis, matching this repo's (in, out) weight layout).
Activations: per-tensor affine — calibrated ranges would come from
representative data; ``quantize_params`` stores weight quant only (the
paper's "full int8" NN path keeps DSP in float, same as we do).

``fake_quant_params`` returns float params that went through the
quantize→dequantize round trip: bit-faithful int8 numerics on any
backend, and the serving path pairs with ``kernels/int8_matmul`` on TPU.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import export as jax_export


# ---------------------------------------------------------------------------
# PrecisionPolicy: the single knob the serving stack threads end-to-end
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class PrecisionPolicy:
    """How params, activations, and the KV cache are represented.

    ``weights``      "float" | "int8"  — int8 wraps projection weights in
                     ``QTensor`` (per-output-channel symmetric int8).
    ``activations``  "dynamic" | "calibrated" — dynamic quantizes each
                     matmul input per row from its own amax; calibrated
                     uses a ``QTensor.amax`` recorded from representative
                     batches (``AmaxObserver``), falling back to dynamic
                     where no amax was attached.
    ``kv_cache``     "float" | "int8" — int8 stores decode caches as
                     ``Int8KV`` (int8 values + per-entry/per-head f32
                     scales).
    ``compute``      "native" | "fake_quant" — native runs the int8
                     kernels; fake_quant runs the quantize→dequantize
                     round trip in float (bit-faithful reference: the
                     serving tier's token-exactness oracle).
    """
    weights: str = "float"
    activations: str = "dynamic"
    kv_cache: str = "float"
    compute: str = "native"

    def __post_init__(self):
        assert self.weights in ("float", "int8"), self.weights
        assert self.activations in ("dynamic", "calibrated"), self.activations
        assert self.kv_cache in ("float", "int8"), self.kv_cache
        assert self.compute in ("native", "fake_quant"), self.compute


FLOAT = PrecisionPolicy()
INT8 = PrecisionPolicy(weights="int8", kv_cache="int8")
INT8_FAKEQUANT = dataclasses.replace(INT8, compute="fake_quant")

_POLICIES = {"float": FLOAT, "int8": INT8,
             "int8_fakequant": INT8_FAKEQUANT}


def policy_for(name) -> PrecisionPolicy:
    """Resolve a CLI-level precision name (or pass a policy through)."""
    if isinstance(name, PrecisionPolicy):
        return name
    if name not in _POLICIES:
        raise ValueError(f"unknown precision {name!r}; "
                         f"one of {sorted(_POLICIES)}")
    return _POLICIES[name]


class QTensor(NamedTuple):
    """A quantized weight: int8 values + per-output-channel f32 scales.

    ``q`` is (..., K, N) int8, ``scale`` (..., N) f32 (leading dims are
    stacked layers, sliced off by ``lax.scan``).  ``amax`` optionally
    carries a calibrated input-activation amax for this matmul site
    (scalar or per-layer (L,)); None means dynamic activation ranges.
    """
    q: jax.Array
    scale: jax.Array
    amax: Optional[jax.Array] = None


class Int8KV(NamedTuple):
    """An int8 KV-cache tensor: values (..., B, S, H, D) int8 + one f32
    scale per cache entry per head, shape (..., B, S, H)."""
    q: jax.Array
    scale: jax.Array


# jax.export serializes pytree defs by name: register both quantized
# containers so int8 decode steps round-trip as CompiledArtifacts.
jax_export.register_namedtuple_serialization(
    QTensor, serialized_name="repro.quantize.QTensor")
jax_export.register_namedtuple_serialization(
    Int8KV, serialized_name="repro.quantize.Int8KV")


@dataclasses.dataclass
class QuantizedParams:
    q: Any           # pytree of int8 arrays (or passthrough float leaves)
    scales: Any      # matching pytree of f32 scales (None = not quantized)
    meta: Dict[str, Any]


def _quant_leaf(w: jax.Array):
    """Per-output-channel symmetric int8 for >=2D float leaves."""
    if w.ndim < 2 or not jnp.issubdtype(w.dtype, jnp.floating):
        return w, None
    axes = tuple(range(w.ndim - 1))
    amax = jnp.max(jnp.abs(w), axis=axes, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(w / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def _dequant_leaf(q, scale):
    if scale is None:
        return q
    return q.astype(jnp.float32) * scale


def quantize_params(params, calib_fn: Optional[Callable] = None
                    ) -> QuantizedParams:
    leaves, treedef = jax.tree.flatten(params)
    qs, ss = [], []
    n_q, total_bytes, q_bytes = 0, 0, 0
    for leaf in leaves:
        q, s = _quant_leaf(leaf)
        qs.append(q)
        ss.append(s)
        total_bytes += leaf.size * leaf.dtype.itemsize
        if s is not None:
            n_q += 1
            q_bytes += q.size + int(np.prod(s.shape)) * 4
        else:
            q_bytes += leaf.size * leaf.dtype.itemsize
    meta = {"n_quantized": n_q, "float_bytes": total_bytes,
            "int8_bytes": q_bytes,
            "compression": total_bytes / max(q_bytes, 1)}
    return QuantizedParams(jax.tree.unflatten(treedef, qs),
                           jax.tree.unflatten(treedef, ss), meta)


def fake_quant_params(qp: QuantizedParams):
    return jax.tree.map(
        lambda q, s: _dequant_leaf(q, s),
        qp.q, qp.scales,
        is_leaf=lambda x: x is None)


def quantization_error(params, qp: QuantizedParams) -> float:
    fq = fake_quant_params(qp)
    errs = jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                           - b.astype(jnp.float32)))),
        params, fq)
    return max(jax.tree.leaves(errs))


# ---------------------------------------------------------------------------
# QAT: straight-through-estimator fake quant for training
# ---------------------------------------------------------------------------
def fake_quant_ste(w: jax.Array) -> jax.Array:
    """Quantize-dequantize with identity gradient (STE)."""
    q, s = _quant_leaf(w)
    if s is None:
        return w
    wq = _dequant_leaf(q, s)
    return w + jax.lax.stop_gradient(wq - w)


def qat_params(params):
    """Apply STE fake quant to every quantizable leaf (wrap a loss with
    this for quantization-aware training)."""
    return jax.tree.map(fake_quant_ste, params)


# ---------------------------------------------------------------------------
# Dynamic activation quantization (per-row symmetric — the serving path)
# ---------------------------------------------------------------------------
def quant_dynamic(x: jax.Array, amax: Optional[jax.Array] = None):
    """Symmetric int8 per-row quantization of a matmul input.

    x: (..., K) float.  Each row (the last-axis vector entering the
    contraction) gets its own scale from its amax, so the int8 matmul's
    per-row × per-channel dequant is exact.  ``amax`` (broadcastable to
    x.shape[:-1]) substitutes a calibrated range for the observed one.
    Returns (q int8 (..., K), scale f32 (...,)).
    """
    x32 = x.astype(jnp.float32)
    if amax is None:
        row_amax = jnp.max(jnp.abs(x32), axis=-1)
    else:
        row_amax = jnp.broadcast_to(
            jnp.asarray(amax, jnp.float32), x32.shape[:-1])
    scale = jnp.maximum(row_amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(x32 / scale[..., None]), -127, 127)
    return q.astype(jnp.int8), scale.astype(jnp.float32)


def fake_quant_dynamic(x: jax.Array,
                       amax: Optional[jax.Array] = None) -> jax.Array:
    """Quantize→dequantize round trip of ``quant_dynamic`` in float —
    bit-faithful simulation of the int8 activation path."""
    q, scale = quant_dynamic(x, amax)
    return q.astype(jnp.float32) * scale[..., None]


# ---------------------------------------------------------------------------
# KV-cache quantization (per-entry/per-head vector scales)
# ---------------------------------------------------------------------------
def quant_kv(x: jax.Array) -> Int8KV:
    """Quantize a KV tensor (..., H, D): one symmetric scale per (entry,
    head) vector of length D."""
    x32 = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x32), axis=-1)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(x32 / scale[..., None]), -127, 127)
    return Int8KV(q.astype(jnp.int8), scale.astype(jnp.float32))


def dequant_kv(kv: Int8KV, dtype=jnp.float32) -> jax.Array:
    return (kv.q.astype(jnp.float32) * kv.scale[..., None]).astype(dtype)


def maybe_quant_kv(policy: Optional[PrecisionPolicy], x: jax.Array):
    """Apply the policy's KV-cache representation to a float KV tensor:
    Int8KV (native), quant→dequant float (fake_quant), or passthrough."""
    if policy is None or policy.kv_cache != "int8":
        return x
    kv = quant_kv(x)
    if policy.compute == "fake_quant":
        return dequant_kv(kv, x.dtype)
    return kv


# ---------------------------------------------------------------------------
# Model-param quantization for the serving path (QTensor pytree)
# ---------------------------------------------------------------------------
# Param sub-trees whose 2D+ leaves feed ops.quant_matmul.  MoE expert
# banks and SSM dynamics keep float (their einsum dispatch never routes
# through the dense matmul entry point); embed/unembed stay out of int8
# so logits keep full precision.
QUANT_SCOPES = ("attn", "mlp", "xattn")
# Top-level tables that every use casts to the activation dtype first
# (``transformer.embed_tokens`` / ``unembed``).
TABLES = ("embed", "unembed")


def _is_projection(path, leaf) -> bool:
    """A >=2-D float leaf under QUANT_SCOPES: a weight that reaches the
    model only through ``ops.quant_matmul``."""
    return (any(getattr(k, "key", None) in QUANT_SCOPES for k in path)
            and leaf.ndim >= 2 and jnp.issubdtype(leaf.dtype, jnp.floating))


@jax.jit
def _leaf_qtensor(w: jax.Array) -> QTensor:
    """Per-output-channel symmetric int8 over the contraction axis (-2),
    keeping per-layer scales for stacked (L, K, N) leaves.  Jitted so
    the elementwise chain fuses: run op by op it would hold several
    f32 copies of the leaf (1.5 GiB each for internlm2-1.8b's stacked
    MLP weights) beside the float model being quantized."""
    amax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=-2)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(w / scale[..., None, :]), -127, 127)
    return QTensor(q.astype(jnp.int8), scale.astype(jnp.float32))


@functools.partial(jax.jit, static_argnums=1)
def _leaf_cast(w: jax.Array, dtype) -> jax.Array:
    return w.astype(dtype)


def _map_params(fn, params):
    """``fn(path, leaf)`` over every leaf; ``QTensor``s pass through
    whole: quantizing twice is quantizing once."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: leaf if isinstance(leaf, QTensor)
        else fn(path, leaf),
        params, is_leaf=lambda x: isinstance(x, QTensor))


def quantize_model_params(params, policy: PrecisionPolicy = INT8):
    """Wrap every projection weight consumed by ``ops.quant_matmul`` in a
    ``QTensor``.  Leaves outside QUANT_SCOPES (embeddings, norms, MoE
    banks, SSM dynamics) pass through untouched, and so do weights that
    are already ``QTensor``s."""
    if policy.weights != "int8":
        return params
    return _map_params(
        lambda path, leaf: (_leaf_qtensor(leaf) if _is_projection(path, leaf)
                            else leaf), params)


def serving_params(params, policy: PrecisionPolicy, dtype):
    """The at-rest form of the weights a server's steps read, made once.

    Projections take the policy's form: ``QTensor`` at int8, ``dtype``
    (the activation dtype) at float.  The ``embed`` / ``unembed`` tables
    are held in ``dtype`` at either precision.  Every step rounds these
    leaves to ``dtype`` before any use, so rounding them once here gives
    the same bits and takes the per-step casts off the device.  Norms,
    SSM dynamics and projections, MoE banks and routers, and ``QTensor``
    scales keep their dtype.  Each leaf is converted in its own jitted
    call, so no leaf is held twice in float32; the caller's tree is not
    touched, and a leaf already in its at-rest form is returned as is.
    """
    dtype = jnp.dtype(dtype)

    def at_rest(path, leaf):
        if policy.weights == "int8" and _is_projection(path, leaf):
            return _leaf_qtensor(leaf)
        table = len(path) == 1 and getattr(path[0], "key", None) in TABLES
        if ((table or _is_projection(path, leaf))
                and leaf.dtype != dtype):
            return _leaf_cast(leaf, dtype)
        return leaf

    return _map_params(at_rest, params)


def attach_act_amax(qparams, amax_by_scope: Dict[str, float]):
    """Attach calibrated activation amax values to QTensor sites, keyed
    by their innermost scope/leaf name (e.g. {"wq": 3.1, "w_down": 8.2}
    or coarser {"attn": 3.5}).  Unmatched sites keep dynamic ranges.

    The amax is broadcast to the leaf's stacked prefix (``q.shape[:-2]``)
    so ``lax.scan`` over stacked layer params slices it alongside the
    weight pair; a per-layer array of that shape passes through as-is.
    """
    def attach(path, leaf):
        if not isinstance(leaf, QTensor):
            return leaf
        for k in reversed(path):
            name = getattr(k, "key", None)
            if name in amax_by_scope:
                amax = jnp.broadcast_to(
                    jnp.asarray(amax_by_scope[name], jnp.float32),
                    leaf.q.shape[:-2])
                return leaf._replace(amax=amax)
        return leaf

    return jax.tree_util.tree_map_with_path(
        attach, qparams, is_leaf=lambda x: isinstance(x, QTensor))


@dataclasses.dataclass
class AmaxObserver:
    """Running activation-amax over representative batches (paper C5's
    calibration step).  ``momentum=None`` tracks the running max;
    otherwise an EMA, which is robust to outlier batches."""
    momentum: Optional[float] = None
    amax: Optional[float] = None

    def update(self, x: jax.Array) -> float:
        cur = float(jnp.max(jnp.abs(x)))
        if self.amax is None:
            self.amax = cur
        elif self.momentum is None:
            self.amax = max(self.amax, cur)
        else:
            self.amax = self.momentum * self.amax + (1 - self.momentum) * cur
        return self.amax


def calibrate_amax(batches, momentum: Optional[float] = None) -> float:
    """Fold representative batches into one calibrated amax."""
    obs = AmaxObserver(momentum=momentum)
    for x in batches:
        obs.update(x)
    assert obs.amax is not None, "no calibration batches given"
    return obs.amax


# ---------------------------------------------------------------------------
# Activation quantization helpers (per-tensor affine)
# ---------------------------------------------------------------------------
def calibrate_activation(x: jax.Array) -> Dict[str, float]:
    lo = float(jnp.min(x))
    hi = float(jnp.max(x))
    scale = max(hi - lo, 1e-8) / 255.0
    zero_point = int(round(-lo / scale)) - 128
    return {"scale": scale, "zero_point": zero_point}


def quant_activation(x: jax.Array, c: Dict[str, float]) -> jax.Array:
    q = jnp.round(x / c["scale"]) + c["zero_point"]
    return jnp.clip(q, -128, 127).astype(jnp.int8)


def dequant_activation(q: jax.Array, c: Dict[str, float]) -> jax.Array:
    return (q.astype(jnp.float32) - c["zero_point"]) * c["scale"]
