"""Split Deconvolution (SD) — the paper's core contribution, in JAX.

Three interchangeable implementations of transposed convolution
("deconvolution"), all bit-identical in f32:

* ``native_deconv``  — reference: ``lax.conv_general_dilated`` with
  ``lhs_dilation`` (what a framework with native deconv support runs).
* ``nzp_deconv``     — Naive Zero Padding baseline: materialise the
  ``s-1`` inserted zeros and run a stride-1 convolution.  This is the
  paper's baseline and deliberately wastes ~``s^d``x MACs.
* ``sd_deconv``      — Split Deconvolution: the deconv filter is split
  offline into ``prod(s)`` stride-1 convolution filters
  (``split_filters``); at runtime one *single grouped* stride-1
  convolution runs on the un-dilated input and a pixel-shuffle
  (``depth_to_space``) interleaves the result.  No inserted zeros ever
  reach the MXU.

Rank generality
---------------
The transform is dimension-agnostic, and so is this module: every
public function here is **rank-polymorphic** over the spatial rank
``d ∈ {1, 2, 3}``.  The rank is inferred from the arrays (``w.ndim - 2``
/ ``x.ndim - 2``) or from tuple-valued geometry arguments; scalar
geometry arguments keep their historical 2-D meaning, so every
pre-existing 2-D call site works unchanged:

* 1-D (audio):      activations ``(B, L, C)``,      filters ``(K, Cin, Cout)``
* 2-D (images):     activations ``(B, H, W, C)``,   filters ``(K_h, K_w, Cin, Cout)``
* 3-D (volumetric): activations ``(B, D, H, W, C)``, filters ``(K_d, K_h, K_w, Cin, Cout)``

Channels are trailing (NHWC-family layouts) and filters are
``(*K, C_in, C_out)`` (HWIO-family); the operation computed by all
implementations is the standard transposed convolution

    out_i = (in_i - 1) * s_i + K_i - p_lo_i - p_hi_i + op_i

identical to ``torch.nn.ConvTranspose{1,2,3}d`` semantics, including
the optional ``output_padding`` (``op``, one extra tap row at the
high end per dim — required for odd output sizes such as 25 -> 50 at
stride 2, where 49 is the default).

The SD math (paper Eqs. 1-13, re-derived 0-based, per dim)
----------------------------------------------------------
With ``K_T = ceil(K/s)`` and ``P_K = s*K_T - K`` (filter zero-expansion
on the *low* side), sub-filter ``n`` (row-major over the per-dim phases
``p_i``) is

    W_n[t, ic, oc] = W_exp[p + s*(K_T-1-t), ic, oc]     (per dim)

(the per-phase 180-degree rotation).  With the input padded by
``P_I = K_T - 1`` on every side, each sub-filter's stride-1 valid conv
output has spatial size ``N + K_T - 1`` per dim, and the pixel-shuffle
``PS[s*v + p] = ConvO_n[v]`` satisfies

    Deconv(I, W)[y] = PS[y + P_K]          (unpadded deconv)

so the full deconv output is a *contiguous crop* of the pixel-shuffled
array — the stride-``s`` DMA write of the paper becomes a pure layout op
(depth_to_space) that XLA folds into the conv epilogue on TPU.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

IntPair = Union[int, Tuple[int, int]]

# Spatial axis letters per rank for lax dimension_numbers.
_SPATIAL = {1: "H", 2: "HW", 3: "DHW"}


def conv_dimension_numbers(rank: int) -> Tuple[str, str, str]:
    """(lhs, rhs, out) dimension-number strings for spatial rank d:
    channels-last activations, ``(*K, I, O)`` filters."""
    sp = _SPATIAL[rank]
    return ("N" + sp + "C", sp + "IO", "N" + sp + "C")


def _ntuple(v, rank: int) -> Tuple[int, ...]:
    """Normalise an int or length-``rank`` sequence to a rank-tuple."""
    if isinstance(v, (tuple, list)):
        if len(v) != rank:
            raise ValueError(f"expected {rank} spatial entries, got {v!r}")
        return tuple(int(x) for x in v)
    return (int(v),) * rank


def _pair(v: IntPair) -> Tuple[int, int]:
    return _ntuple(v, 2)


def _pads_nd(padding, rank: int) -> Tuple[Tuple[int, int], ...]:
    """Normalise padding to ``((lo, hi),) * rank``.

    Accepts: int ``p``; a length-``rank`` sequence of ints (symmetric
    per dim); or a length-``rank`` sequence of ``(lo, hi)`` pairs.  For
    rank 1 a bare ``(lo, hi)`` int pair is read as the explicit
    low/high padding of the single spatial dim.
    """
    if isinstance(padding, int):
        return ((padding, padding),) * rank
    seq = tuple(padding)
    if rank == 1 and len(seq) == 2 and all(isinstance(a, int) for a in seq):
        return ((int(seq[0]), int(seq[1])),)
    if len(seq) != rank:
        raise ValueError(f"padding {padding!r} does not match rank {rank}")
    out = []
    for a in seq:
        if isinstance(a, int):
            out.append((a, a))
        else:
            lo, hi = a
            out.append((int(lo), int(hi)))
    return tuple(out)


def _pads(padding) -> Tuple[Tuple[int, int], Tuple[int, int]]:
    """2-D shim: normalise padding to ((top, bottom), (left, right))."""
    return _pads_nd(padding, 2)


def _check_padding(kernel: Sequence[int], padding) -> None:
    """Shared validation: every deconv implementation must reject the same
    inputs the same way (cropping more than K-1 is meaningless — it would
    discard whole taps)."""
    k = tuple(int(x) for x in kernel)
    pads = _pads_nd(padding, len(k))
    for ki, (lo, hi) in zip(k, pads):
        if ki - 1 - lo < 0 or ki - 1 - hi < 0:
            raise ValueError(f"padding {padding} too large for kernel {k}")


def _check_output_padding(output_padding: Tuple[int, ...],
                          stride: Tuple[int, ...]) -> None:
    """``0 <= op < s`` per dim (torch ConvTransposeNd's constraint: one
    extra output row per dim at most, and only where a real tap lands)."""
    for op, s in zip(output_padding, stride):
        if op < 0 or op >= max(s, 1):
            raise ValueError(
                f"output_padding {output_padding} must satisfy "
                f"0 <= op < stride {stride} per dim")


def same_deconv_pads(kernel, stride):
    """TF conv_transpose 'SAME' crop amounts (out = in*s) per dim.

    Scalar args keep the historical 2-D meaning; pass rank-tuples for
    1-D/3-D.
    """
    rank = len(kernel) if isinstance(kernel, (tuple, list)) else (
        len(stride) if isinstance(stride, (tuple, list)) else 2)
    k, s = _ntuple(kernel, rank), _ntuple(stride, rank)
    pads = []
    for ki, si in zip(k, s):
        a = max(ki - si, 0)
        pads.append((a // 2, a - a // 2))
    return tuple(pads)


def deconv_output_shape(in_space: Sequence[int], kernel, stride,
                        padding=0, output_padding=0) -> Tuple[int, ...]:
    """Spatial output shape of a transposed conv:
    ``(in-1)*s + K - p_lo - p_hi + op`` per dim (rank = len(in_space))."""
    rank = len(in_space)
    k, s = _ntuple(kernel, rank), _ntuple(stride, rank)
    pads = _pads_nd(padding, rank)
    op = _ntuple(output_padding, rank)
    return tuple((n - 1) * si + ki - lo - hi + opi
                 for n, ki, si, (lo, hi), opi
                 in zip(in_space, k, s, pads, op))


# ---------------------------------------------------------------------------
# Reference implementations
# ---------------------------------------------------------------------------

def native_deconv(x: jax.Array, w: jax.Array, stride,
                  padding=0, output_padding=0) -> jax.Array:
    """Transposed conv via lax.conv_general_dilated (lhs_dilation).

    x: (B, *S, C_in); w: (*K, C_in, C_out) — rank inferred from w.
    """
    rank = w.ndim - 2
    s = _ntuple(stride, rank)
    k = tuple(w.shape[:rank])
    pads = _pads_nd(padding, rank)
    op = _ntuple(output_padding, rank)
    _check_padding(k, padding)
    _check_output_padding(op, s)
    flip = w[tuple(slice(None, None, -1) for _ in range(rank))]
    return lax.conv_general_dilated(
        x, flip,                                # 180-degree spatial rotation
        window_strides=(1,) * rank,
        padding=[(ki - 1 - lo, ki - 1 - hi + opi)
                 for ki, (lo, hi), opi in zip(k, pads, op)],
        lhs_dilation=s,
        dimension_numbers=conv_dimension_numbers(rank),
    )


def dilate_input(x: jax.Array, stride) -> jax.Array:
    """Insert (s-1) zeros between spatial elements: the NZP materialisation."""
    rank = x.ndim - 2
    s = _ntuple(stride, rank)
    space = x.shape[1:1 + rank]
    out_space = tuple((n - 1) * si + 1 for n, si in zip(space, s))
    out = jnp.zeros((x.shape[0], *out_space, x.shape[-1]), x.dtype)
    idx = (slice(None),) + tuple(slice(None, None, si) for si in s)
    return out.at[idx].set(x)


def nzp_deconv(x: jax.Array, w: jax.Array, stride,
               padding=0, output_padding=0) -> jax.Array:
    """Naive Zero Padding baseline: materialised dilation + stride-1 conv.

    Bit-identical to ``native_deconv`` but performs the full redundant
    computation the paper measures (Table 2, 'Naive Zero-padding').
    """
    rank = w.ndim - 2
    s = _ntuple(stride, rank)
    k = tuple(w.shape[:rank])
    pads = _pads_nd(padding, rank)
    op = _ntuple(output_padding, rank)
    _check_padding(k, padding)
    _check_output_padding(op, s)
    xd = dilate_input(x, s)
    flip = w[tuple(slice(None, None, -1) for _ in range(rank))]
    return lax.conv_general_dilated(
        xd, flip,
        window_strides=(1,) * rank,
        padding=[(ki - 1 - lo, ki - 1 - hi + opi)
                 for ki, (lo, hi), opi in zip(k, pads, op)],
        dimension_numbers=conv_dimension_numbers(rank),
    )


# ---------------------------------------------------------------------------
# Split Deconvolution
# ---------------------------------------------------------------------------

def sd_geometry(kernel, stride):
    """(K_T, P_K, P_I) per spatial dim — paper Eqs. (1), (2), (9).

    Scalar args keep the historical 2-D meaning (returns 2-tuples);
    tuple args set the rank.
    """
    rank = len(kernel) if isinstance(kernel, (tuple, list)) else (
        len(stride) if isinstance(stride, (tuple, list)) else 2)
    k, s = _ntuple(kernel, rank), _ntuple(stride, rank)
    kt = tuple(-(-ki // si) for ki, si in zip(k, s))        # ceil
    pk = tuple(si * kti - ki for ki, si, kti in zip(k, s, kt))
    pi = tuple(kti - 1 for kti in kt)
    return kt, pk, pi


def split_filters(w: jax.Array, stride) -> jax.Array:
    """Offline filter transform (paper steps 1+2, Eqs. 1-8), any rank.

    w: (*K, C_in, C_out)  ->  (*K_T, C_in, prod(s)*C_out).

    Output channel layout is n-major: channel ``n*C_out + oc`` holds
    sub-filter ``n`` (row-major over the per-dim phases), which is
    exactly what ``depth_to_space`` expects.
    """
    rank = w.ndim - 2
    s = _ntuple(stride, rank)
    k = w.shape[:rank]
    cin, cout = w.shape[rank], w.shape[rank + 1]
    kt, pk, _ = sd_geometry(k, s)
    # 1) expand with zeros on the LOW side of every spatial dim (paper:
    #    guarantees the pixel-shuffled output is the deconv output
    #    cropped by P_K).
    we = jnp.pad(w, [(p, 0) for p in pk] + [(0, 0), (0, 0)])
    # 2) sample with stride s and rotate 180 deg per sub-filter.
    #    index u = m*s + p  ->  (m, p); tap t = K_T-1-m  (the rotation).
    shape = []
    for kti, si in zip(kt, s):
        shape += [kti, si]
    we = we.reshape(*shape, cin, cout)
    flip = tuple(slice(None, None, -1) if (i % 2 == 0 and i < 2 * rank)
                 else slice(None) for i in range(2 * rank + 2))
    we = we[flip]                                       # flip every m axis
    perm = ([2 * i for i in range(rank)] + [2 * rank]
            + [2 * i + 1 for i in range(rank)] + [2 * rank + 1])
    we = we.transpose(perm)                 # (*kt, cin, *s, cout)
    return we.reshape(*kt, cin, math.prod(s) * cout)


def unsplit_filters(ws: jax.Array, kernel, stride) -> jax.Array:
    """Exact inverse (== linear adjoint) of :func:`split_filters`.

    ``split_filters`` is a zero-pad followed by a permutation, so its
    adjoint is the inverse permutation followed by the crop of the
    ``P_K`` expansion zeros.  This is what maps split-layout filter
    *gradients* back onto the original deconv filter, and also the
    "compressed SD" storage transform of paper Table 3.
    """
    rank = ws.ndim - 2
    s = _ntuple(stride, rank)
    k = _ntuple(kernel, rank)
    kt, pk, _ = sd_geometry(k, s)
    cin = ws.shape[rank]
    cout = ws.shape[-1] // math.prod(s)
    we = ws.reshape(*kt, cin, *s, cout)
    perm = ([2 * i for i in range(rank)] + [2 * rank]
            + [2 * i + 1 for i in range(rank)] + [2 * rank + 1])
    inv = [0] * len(perm)
    for i, p in enumerate(perm):
        inv[p] = i
    we = we.transpose(inv)                  # (kt0, s0, kt1, s1, ..., cin, cout)
    flip = tuple(slice(None, None, -1) if (i % 2 == 0 and i < 2 * rank)
                 else slice(None) for i in range(2 * rank + 2))
    we = we[flip]                           # undo the m-flips
    we = we.reshape(*[si * kti for si, kti in zip(s, kt)], cin, cout)
    crop = tuple(slice(p, None) for p in pk)
    return we[crop]                         # crop the expansion pad


def depth_to_space(y: jax.Array, stride) -> jax.Array:
    """Pixel-shuffle: (B, *S, prod(s)*C) -> (B, *(s*S), C), n-major layout.

    This is the TPU-native realisation of the paper's stride-s DMA write
    (output reorganisation, Eqs. 10-13); rank inferred from ``y``.
    """
    rank = y.ndim - 2
    s = _ntuple(stride, rank)
    b = y.shape[0]
    space = y.shape[1:1 + rank]
    cout = y.shape[-1] // math.prod(s)
    y = y.reshape(b, *space, *s, cout)
    perm = [0]
    for i in range(rank):
        perm += [1 + i, 1 + rank + i]
    perm += [1 + 2 * rank]
    y = y.transpose(perm)                   # (b, S0, s0, S1, s1, ..., c)
    return y.reshape(b, *[n * si for n, si in zip(space, s)], cout)


def space_to_depth(x: jax.Array, stride) -> jax.Array:
    """Inverse pixel-shuffle (used by the SD backward pass and the VLM
    patch-embed / Mamba fold paths)."""
    rank = x.ndim - 2
    s = _ntuple(stride, rank)
    b = x.shape[0]
    space = x.shape[1:1 + rank]
    c = x.shape[-1]
    shape = []
    for n, si in zip(space, s):
        shape += [n // si, si]
    x = x.reshape(b, *shape, c)
    perm = ([0] + [1 + 2 * i for i in range(rank)]
            + [2 + 2 * i for i in range(rank)] + [1 + 2 * rank])
    x = x.transpose(perm)
    return x.reshape(b, *[n // si for n, si in zip(space, s)],
                     math.prod(s) * c)


def crop_interleaved(ps: jax.Array, pk, pads, out_space) -> jax.Array:
    """P_K + user-padding crop of the interleaved (pixel-shuffled)
    output; zero-extends first when ``output_padding`` reaches past the
    shuffled support (op > high crop).  Shared by the XLA path and the
    fused-kernel paths in :mod:`repro.kernels.ops`."""
    starts = [pki + lo for pki, (lo, _) in zip(pk, pads)]
    limits = [st + o for st, o in zip(starts, out_space)]
    grow = [max(0, lim - ps.shape[1 + i]) for i, lim in enumerate(limits)]
    if any(grow):
        ps = jnp.pad(ps, [(0, 0)] + [(0, g) for g in grow] + [(0, 0)])
    return lax.slice(ps, (0, *starts, 0),
                     (ps.shape[0], *limits, ps.shape[-1]))


def sd_deconv_presplit(x: jax.Array, ws: jax.Array, kernel,
                       stride, padding=0,
                       conv_fn=None, output_padding=0) -> jax.Array:
    """Runtime SD (paper steps 3+4) given pre-split filters ``ws``.

    ``ws`` is the output of :func:`split_filters`; splitting is offline and
    reused across inference calls, as in the paper.
    ``conv_fn(x, w)`` may override the stride-1 VALID convolution (e.g. the
    Pallas kernel); default is XLA's conv.  Rank inferred from ``x``.
    """
    rank = x.ndim - 2
    s = _ntuple(stride, rank)
    k = _ntuple(kernel, rank)
    pads = _pads_nd(padding, rank)
    op = _ntuple(output_padding, rank)
    _check_padding(k, padding)
    _check_output_padding(op, s)
    kt, pk, pi = sd_geometry(k, s)
    out_space = deconv_output_shape(x.shape[1:1 + rank], k, s, padding,
                                    output_padding)

    # step 3: pad the input with P_I zeros per side; one grouped stride-1
    # conv computes all prod(s) sub-filter outputs in a single GEMM-shaped
    # op.
    xp = jnp.pad(x, [(0, 0)] + [(p, p) for p in pi] + [(0, 0)])
    if conv_fn is None:
        y = lax.conv_general_dilated(
            xp, ws, window_strides=(1,) * rank, padding="VALID",
            dimension_numbers=conv_dimension_numbers(rank))
    else:
        y = conv_fn(xp, ws)
    # step 4: interleave (pixel-shuffle) + crop P_K (+ user padding p).
    # output_padding rows past the bottom crop extend the window; any
    # rows past the unpadded deconv support (op > p_hi) are zeros.
    ps = depth_to_space(y, s)
    return crop_interleaved(ps, pk, pads, out_space)


def sd_deconv(x: jax.Array, w: jax.Array, stride,
              padding=0, conv_fn=None, output_padding=0) -> jax.Array:
    """Split Deconvolution, end to end (splits filters inline), any rank.

    Prefer :func:`split_filters` + :func:`sd_deconv_presplit` in real
    deployments so the offline transform is amortised.
    """
    rank = w.ndim - 2
    ws = split_filters(w, stride)
    return sd_deconv_presplit(x, ws, w.shape[:rank], stride, padding,
                              conv_fn, output_padding)


def sd_deconv_paper(x: jax.Array, w: jax.Array, stride: IntPair,
                    padding=0) -> jax.Array:
    """Paper-faithful SD deployment (2-D): ``s^2`` *separate sequential*
    small convolutions (the edge-processor execution model of Algorithm 2)
    whose outputs are interleaved by the stride-s write.

    Numerically identical to :func:`sd_deconv`; on TPU the grouped
    single-conv formulation (sd_deconv) reuses each input tile for all
    s^2 sub-filters in one GEMM — the beyond-paper optimisation measured
    in benchmarks/sd_roofline.py.
    """
    sh, sw = _pair(stride)
    (pt, pb), (pl, pr) = _pads(padding)
    kernel = w.shape[:2]
    _check_padding(kernel, padding)
    (kth, ktw), (pkh, pkw), (pih, piw) = sd_geometry(kernel, (sh, sw))
    oh, ow = deconv_output_shape(x.shape[1:3], kernel, (sh, sw), padding)
    ws = split_filters(w, (sh, sw))          # (KT,KT,Cin,s*s*Cout)
    cout = w.shape[3]
    xp = jnp.pad(x, ((0, 0), (pih, pih), (piw, piw), (0, 0)))
    outs = []
    for n in range(sh * sw):                 # paper: one conv per split
        wn = lax.slice_in_dim(ws, n * cout, (n + 1) * cout, axis=3)
        outs.append(lax.conv_general_dilated(
            xp, wn, window_strides=(1, 1), padding="VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC")))
    y = jnp.concatenate(outs, axis=-1)       # n-major channel layout
    ps = depth_to_space(y, (sh, sw))
    return lax.slice(ps, (0, pkh + pt, pkw + pl, 0),
                     (ps.shape[0], pkh + pt + oh, pkw + pl + ow,
                      ps.shape[3]))


# ---------------------------------------------------------------------------
# Standard convolution helpers (shared by models)
# ---------------------------------------------------------------------------

def conv_nd(x: jax.Array, w: jax.Array, stride=1,
            padding="SAME") -> jax.Array:
    """Plain channels-last cross-correlation, any rank (the op CNN
    processors run).  HIGHEST precision: f32 operands stay f32 on the
    TPU's MXU (its default rounds them to bf16); bf16 is unaffected."""
    rank = w.ndim - 2
    s = _ntuple(stride, rank)
    if isinstance(padding, int):
        padding = [(padding, padding)] * rank
    return lax.conv_general_dilated(
        x, w, window_strides=s, padding=padding,
        dimension_numbers=conv_dimension_numbers(rank),
        precision=lax.Precision.HIGHEST)


def conv2d(x: jax.Array, w: jax.Array, stride: IntPair = 1,
           padding="SAME") -> jax.Array:
    """2-D shim over :func:`conv_nd` (NHWC/HWIO)."""
    return conv_nd(x, w, stride, padding)
