"""Symmetric int8 quantization — the numerics substrate of the
low-precision edge path (HUGE\\ :sup:`2`, arXiv:1907.11210).

One module owns every int8 helper in the repo:

* :func:`quantize` / :func:`dequantize` — per-**tensor** scale.  These
  are the primitives :mod:`repro.distributed.compress` has always used
  for the gradient-compression hop; they were promoted here so the
  inference path and the transport path share one rounding convention
  (symmetric, zero-point 0, clip to ±127 — so a zero stays exactly
  zero, which is what keeps the Pallas kernels' zero-padded halo exact
  *in int8*).
* :func:`quantize_channelwise` — per-**channel** scales along one axis.
  This is the filter quantizer: :meth:`repro.sd.DeconvPlan.bind` calls
  it on the split (scale-folded) filters with ``axis=-1``, so every
  split output channel — each (phase, oc) pair of the paper's
  transform — carries its own scale, folded together with the
  inference-BN scale exactly like the fp32 path folds gamma.
* :func:`quantize_act` — per-**sample** scale over a batched
  activation.  Dynamic (computed in-trace per call); per sample rather
  than per tensor so the zero rows a bucketed server pads a batch with
  can never perturb real samples' quantization (regression-tested).

* :func:`quantize_static` — quantization against a **pre-computed**
  (calibration-time) scale, with *saturating-clamp* semantics: values
  beyond the calibrated range land on ±127, never wrap.  This is the
  activation-chaining quantizer — no reduction runs on the hot path.
* :func:`amax_stat` / :func:`scale_from_amax` — the calibration
  statistics (max / percentile policy) behind the static scales, and
  the on-disk calibration cache (:func:`load_calib` /
  :func:`save_calib`) persisted next to the autotune plan cache via
  the shared atomic-write idiom (:mod:`repro.core.iohelpers`).

All scales are ``amax / 127`` floats; dequantization is a per-channel
(or per-sample) multiply, which the fused kernel folds into its VMEM
epilogue (see :mod:`repro.kernels.sd_conv`).
"""

from __future__ import annotations

import os
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.iohelpers import atomic_write_json, read_json

QMAX = 127.0          # symmetric int8: [-127, 127], zero-point 0
_EPS = 1e-12          # all-zero tensors quantize to zeros, not NaNs


def _to_q(xf: jax.Array, scale: jax.Array) -> jax.Array:
    return jnp.clip(jnp.round(xf / scale), -QMAX, QMAX).astype(jnp.int8)


def quantize(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Symmetric int8 with one per-tensor scale: ``(q, scale)`` with
    ``x ≈ q * scale``."""
    xf = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(xf)), _EPS) / QMAX
    return _to_q(xf, scale), scale


def dequantize(q: jax.Array, scale: jax.Array, dtype=jnp.float32):
    return (q.astype(jnp.float32) * scale).astype(dtype)


def quantize_channelwise(w: jax.Array,
                         axis: int = -1) -> Tuple[jax.Array, jax.Array]:
    """Symmetric int8 with one scale per slice of ``axis``.

    Returns ``(q, scales)`` where ``scales`` is 1-D of length
    ``w.shape[axis]`` and ``w ≈ q * scales`` (broadcast along
    ``axis``).  This is the filter quantizer: with ``axis=-1`` on
    n-major split filters every (phase, oc) output channel of the
    executed stride-1 conv gets its own scale, so the worst-case
    rounding error per channel is ``scales[c] / 2`` regardless of how
    skewed the channel magnitudes are.
    """
    axis = axis % w.ndim
    wf = w.astype(jnp.float32)
    reduce_axes = tuple(i for i in range(w.ndim) if i != axis)
    amax = jnp.max(jnp.abs(wf), axis=reduce_axes)
    scales = jnp.maximum(amax, _EPS) / QMAX
    shape = [1] * w.ndim
    shape[axis] = w.shape[axis]
    return _to_q(wf, scales.reshape(shape)), scales


def quantize_act(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Dynamic symmetric int8 for a batched activation: one scale per
    *sample* (axis 0), computed in-trace.

    Returns ``(q, scales)`` with ``scales`` of shape ``(B,)``.
    Per-sample rather than per-tensor so batch composition never leaks
    between requests: the zero padding a bucketed server appends to a
    group cannot change any real sample's scale, and sample ``i``'s
    quantized output is a function of sample ``i`` alone.
    """
    xf = x.astype(jnp.float32)
    axes = tuple(range(1, x.ndim))
    amax = jnp.max(jnp.abs(xf), axis=axes)
    scales = jnp.maximum(amax, _EPS) / QMAX
    shape = (x.shape[0],) + (1,) * (x.ndim - 1)
    return _to_q(xf, scales.reshape(shape)), scales


# ---------------------------------------------------------------------------
# Static calibration: pre-computed scales, saturating clamp, scale cache.
# ---------------------------------------------------------------------------


def quantize_static(x: jax.Array, scale) -> jax.Array:
    """Quantize against a *static* (calibration-time) scale — no
    reduction, no data-dependence, so the hot path carries zero amax
    passes and zero-padded bucket rows can never perturb real samples.

    Saturating-clamp semantics for out-of-calibration activations:
    ``x / scale`` beyond ±127 clamps to ±127 (``jnp.clip`` before the
    int8 cast — never a two's-complement wrap), and non-finite inputs
    (inf from an upstream overflow) saturate the same way rather than
    poisoning the int8 tensor.  Exact zeros stay exactly zero.
    """
    xf = x.astype(jnp.float32)
    q = jnp.round(xf / jnp.asarray(scale, jnp.float32))
    # NaN-safe saturation: clip handles ±inf; a NaN input quantizes to
    # 0 (the only value that cannot masquerade as signal).
    q = jnp.clip(q, -QMAX, QMAX)
    q = jnp.where(jnp.isnan(q), 0.0, q)
    return q.astype(jnp.int8)


def amax_stat(x: jax.Array, policy: str = "max",
              pct: float = 99.9) -> jax.Array:
    """One calibration statistic of ``|x|`` over the whole tensor.

    ``policy="max"`` is the exact amax (no clipping on calibration
    data); ``policy="pct"`` is the ``pct``-th percentile of ``|x|`` —
    the AWQ-style choice that trades a little saturation on the tail
    for finer resolution of the bulk.  Returns a scalar f32 array;
    deterministic for a fixed input (pure jnp reductions).
    """
    a = jnp.abs(x.astype(jnp.float32)).reshape(-1)
    if policy == "max":
        return jnp.max(a)
    if policy == "pct":
        return jnp.percentile(a, pct)
    raise ValueError(f"unknown calibration policy {policy!r}; "
                     "choose from ('max', 'pct')")


def scale_from_amax(amax) -> float:
    """The symmetric int8 scale for a calibrated amax (floored at _EPS
    so an all-zero calibration tensor yields a finite scale)."""
    return float(max(float(amax), _EPS) / QMAX)


# Calibration-scale cache: {"version": 1, "scales": {key: {layer: s}}}.
# Lives next to the autotune plan cache, same atomic-write discipline.
_ENV_CALIB = "REPRO_SD_CALIB_CACHE"
_DEFAULT_CALIB = os.path.join(os.path.expanduser("~"), ".cache", "repro",
                              "sd_calib.json")


def calib_cache_path(path: Optional[str] = None) -> str:
    return path or os.environ.get(_ENV_CALIB, _DEFAULT_CALIB)


def load_calib(key: str,
               path: Optional[str] = None) -> Optional[Dict[str, float]]:
    """Per-layer static activation scales recorded under ``key`` (e.g.
    ``"dcgan/max"``), or None when the cache has no entry."""
    data = read_json(calib_cache_path(path))
    if not isinstance(data, dict):
        return None
    entry = data.get("scales", {}).get(key)
    if not isinstance(entry, dict):
        return None
    return {str(k): float(v) for k, v in entry.items()}


def save_calib(key: str, scales: Dict[str, float],
               path: Optional[str] = None) -> str:
    """Persist per-layer scales under ``key`` (read-modify-write of the
    whole document; the atomic replace keeps concurrent writers from
    tearing it — last writer wins per key)."""
    p = calib_cache_path(path)
    data = read_json(p)
    if not isinstance(data, dict):
        data = {}
    scales_all = dict(data.get("scales", {}))
    scales_all[key] = {str(k): float(v) for k, v in scales.items()}
    atomic_write_json(p, {"version": 1, "scales": scales_all})
    return p
