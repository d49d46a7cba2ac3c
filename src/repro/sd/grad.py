"""The backward pass of split deconvolution, as standard convolutions.

This is what makes :func:`repro.sd.conv_transpose` differentiable even
when its forward runs through the fused Pallas kernel (which has no
autodiff rule): the ``custom_vjp`` backward never differentiates the
forward — it *is* the paper's transform applied to the adjoint problem,
and every compute-heavy step is a dense stride-1 convolution, i.e. the
same op class the paper keeps the processor on.  Everything below is
rank-polymorphic (1-D/2-D/3-D), like the forward.

Derivation.  The forward (``core.sd_deconv_presplit``) is

    xp  = pad(x, P_I)                                    (static zeros)
    y1  = conv_valid(xp, ws)          ws = split_filters(w)   [the GEMM]
    ps  = depth_to_space(y1)                              (permutation)
    y   = crop(ps, P_K + user padding, + output_padding) (+ b)

Each step is linear, so the VJP is the chain of adjoints, right to left:

* crop^T      — zero-embed the cotangent ``dy`` back into the ps array
                (output_padding rows past the shuffled support were
                zeros in the forward: their cotangent is dropped);
* d2s^T       — ``space_to_depth`` (d2s is a permutation);
* conv^T(x)   — the input grad of a stride-1 VALID correlation: a FULL
                stride-1 conv of ``dy1`` with the split filters rotated
                180 deg and in/out channels swapped;
* conv^T(w)   — the filter grad: a stride-1 VALID conv with batch and
                channel axes exchanged (``xp`` as lhs feature maps,
                ``dy1`` as the filter bank);
* split^T     — :func:`repro.core.deconv.unsplit_filters` (inverse
                permutation + crop of the expansion zeros) maps the
                split-layout filter grad onto the original ``w``;
* pad^T       — crop the ``P_I`` halo off the input grad.

Kernel routing.  For a ``backend="fused"`` plan of rank 1 or 2 the two
convolutions above run through the Pallas kernels
(:func:`repro.kernels.ops.sd_input_grad_fused` — the FULL-conv pad is
the launch's zero pad and the pad^T crop is the launch's output
window — and :func:`repro.kernels.ops.sd_filter_grad_fused`, whose
launch applies the ``P_I`` activation pad),
each under its own tagged ``ConvGeom`` autotune key; 1-D lowers as H=1
2-D exactly like the forward.  The fused backend is therefore trainable
on-kernel, not just differentiable-by-fallback.  ``backend="xla"`` (and
rank 3) keep the ``lax.conv_general_dilated`` formulation below.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.deconv import (conv_dimension_numbers, sd_geometry,
                               space_to_depth, split_filters,
                               unsplit_filters)
from .plan import DeconvPlan


def _conv_valid_input_grad(dy1: jax.Array, ws: jax.Array) -> jax.Array:
    """VJP of ``y1 = conv_valid_stride1(xp, ws)`` w.r.t. ``xp``: a FULL
    stride-1 conv with the spatially-rotated, channel-swapped filters."""
    rank = dy1.ndim - 2
    kt = ws.shape[:rank]
    w_t = ws[tuple(slice(None, None, -1) for _ in range(rank))]
    w_t = jnp.swapaxes(w_t, -1, -2)                # rot180, swap ic/oc
    return lax.conv_general_dilated(
        dy1, w_t, window_strides=(1,) * rank,
        padding=[(kti - 1, kti - 1) for kti in kt],
        dimension_numbers=conv_dimension_numbers(rank))


def _conv_valid_filter_grad(xp: jax.Array, dy1: jax.Array) -> jax.Array:
    """VJP of ``y1 = conv_valid_stride1(xp, ws)`` w.r.t. ``ws``: a VALID
    stride-1 conv treating channels as batch and batch as channels."""
    rank = xp.ndim - 2
    spatial = tuple(range(1, rank + 1))
    lhs = xp.transpose((rank + 1,) + spatial + (0,))   # (Cin, *Sp, B)
    rhs = dy1.transpose(spatial + (0, rank + 1))       # (*O1, B, N*Co)
    out = lax.conv_general_dilated(
        lhs, rhs, window_strides=(1,) * rank, padding="VALID",
        dimension_numbers=conv_dimension_numbers(rank))
    return out.transpose(spatial + (0, rank + 1))      # (*KT, Cin, N*Co)


def _use_pallas_bwd(plan: DeconvPlan) -> bool:
    """Fused-backend plans of rank 1/2 run the backward convs on the
    Pallas kernels (1-D lowers as H=1 2-D); rank 3 and the xla backend
    keep the lax formulation."""
    return plan.backend == "fused" and plan.rank <= 2


def _pallas_input_grad(plan: DeconvPlan, dy1: jax.Array, ws: jax.Array,
                       pi, space) -> jax.Array:
    from repro.kernels import ops                     # lazy: pulls Pallas
    if plan.rank == 1:
        dx = ops.sd_input_grad_fused(dy1[:, None], ws[None],
                                     (0, pi[0]), (1, space[0]))
        return dx[:, 0]
    return ops.sd_input_grad_fused(dy1, ws, tuple(pi), tuple(space))


def _pallas_filter_grad(plan: DeconvPlan, x: jax.Array, dy1: jax.Array,
                        kt, pi) -> jax.Array:
    from repro.kernels import ops                     # lazy: pulls Pallas
    if plan.rank == 1:
        dws = ops.sd_filter_grad_fused(x[:, None], dy1[:, None],
                                       (1, kt[0]), (0, pi[0]))
        return dws[0]
    return ops.sd_filter_grad_fused(x, dy1, tuple(kt), tuple(pi))


def conv_transpose_vjp(plan: DeconvPlan, x: jax.Array, w: jax.Array,
                       dy: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """``(dx, dw)`` for ``y = conv_transpose(plan, x, w)``.

    Both gradients are computed over the *split layout* — the cotangent
    is pixel-unshuffled once and the two convolutions above run on
    ``K_T``-tap stride-1 geometry, so the backward enjoys the same
    no-inserted-zeros property as the forward.

    Cout-sharded plans (``plan.shards > 1`` under ``shard_map``): ``w``
    is this device's Cout slice and ``dy`` the full-channel cotangent
    of the all-gathered forward output (replicated over the shard
    axis).  The gather's adjoint is a slice: take this device's channel
    block of ``dy`` and run the identical local backward — the filter
    grad then *stays local to the shard* (it only ever touches local
    channels, mirroring the sharded filter primal), and only the input
    grad, a sum over all output channels, needs one ``psum``.
    """
    rank = plan.rank
    kt, pk, pi = sd_geometry(plan.kernel, plan.stride)
    space = x.shape[1:1 + rank]
    ws = split_filters(w, plan.stride)
    if plan.shards > 1:
        # all_gather^T: this shard's Cout block of the cotangent.
        coutl = w.shape[-1]
        start = lax.axis_index(plan.shard_axis) * coutl
        dy = lax.dynamic_slice_in_dim(dy, start, coutl, axis=dy.ndim - 1)

    # crop^T: embed dy at offset (P_K + low crop); the trailing margin
    # per dim is (high crop - output_padding).  When output_padding grew
    # past the shuffled support (op > hi) the forward zero-extended —
    # drop those rows' cotangent before embedding.
    pad_cfg = [(0, 0)]
    for i, ((lo, hi), opi) in enumerate(zip(plan.padding,
                                            plan.output_padding)):
        trail = hi - opi
        if trail < 0:
            dy = lax.slice_in_dim(dy, 0, dy.shape[1 + i] + trail,
                                  axis=1 + i)
            trail = 0
        pad_cfg.append((pk[i] + lo, trail))
    pad_cfg.append((0, 0))
    dps = jnp.pad(dy, pad_cfg)
    dy1 = space_to_depth(dps, plan.stride)         # d2s^T

    if _use_pallas_bwd(plan):
        dx = _pallas_input_grad(plan, dy1, ws.astype(dy1.dtype), pi,
                                space)
        dws = _pallas_filter_grad(plan, x, dy1, kt, pi)
    else:
        dxp = _conv_valid_input_grad(dy1, ws.astype(dy1.dtype))
        dx = dxp[(slice(None),)                    # pad^T
                 + tuple(slice(p, p + n) for p, n in zip(pi, space))]
        xp = jnp.pad(x, [(0, 0)] + [(p, p) for p in pi] + [(0, 0)])
        dws = _conv_valid_filter_grad(xp, dy1)
    dw = unsplit_filters(dws, plan.kernel, plan.stride)    # split^T
    if plan.shards > 1:
        # dx sums over *all* output channels; each shard saw its own.
        dx = lax.psum(dx, plan.shard_axis)
    return dx.astype(x.dtype), dw.astype(w.dtype)
