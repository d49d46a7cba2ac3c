"""jit'd public wrappers around the Pallas kernels.

The fused deconv path writes each output once, at final geometry: the
``P_K`` + user-padding crop is folded into the epilogue (offset band +
phase-offset stores + trimmed ``out_shape``), and row/col grids
ceil-divide the output so no alignment padding exists.  The ``P_I``
input pad is one zero pad per launch (Mosaic refuses element windows
below the array).  The old pad -> kernel -> crop composition survives
as ``zero_copy=False`` — it is the reference the parity tests and the
CI HBM-traffic gate compare against.

These wrappers run the kernels compiled on TPU and in Pallas interpret
mode on any other backend (the CPU test suite), and consult
the autotuner (:mod:`repro.kernels.autotune`) for ``(th, tw, tcin,
tcout)`` tile plans when the caller does not pin one — the hardcoded
row-tile heuristic of the seed lives on only as the autotuner's
fallback.  The backward's two stride-1 convolutions
(:func:`sd_input_grad_fused`, :func:`sd_filter_grad_fused`) run through
the same kernels under their own tagged ``ConvGeom`` plan keys — the
fused backend is trainable on-kernel (see :mod:`repro.sd.grad`).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.deconv import (_check_output_padding, _check_padding,
                               _ntuple, _pads, _pads_nd, crop_interleaved,
                               deconv_output_shape, depth_to_space,
                               sd_geometry, split_filters)
from . import autotune
from . import sd_conv as _k
from . import winograd as _wk
from .autotune import ConvGeom, KernelPlan

PadPair = Tuple[int, int]


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _resolve_plan(geom: ConvGeom, th, tcin, tcout,
                  tw=None) -> KernelPlan:
    """Fill unpinned tile params from the autotuner's plan cache.

    Fully pinned calls (the engine's hot path) skip the lookup entirely;
    ``tw`` rides along with the pin (``None`` -> full-width bands, the
    pre-``tw`` behaviour of pinned callers).
    """
    if th and tcin and tcout:
        return KernelPlan(th=th, tcin=tcin, tcout=tcout, tw=tw or 0)
    plan = autotune.get_plan(geom)
    return KernelPlan(th=th or plan.th, tcin=tcin or plan.tcin,
                      tcout=tcout or plan.tcout,
                      tw=plan.tw if tw is None else tw)


def _plan_kwargs(plan: Optional[KernelPlan]) -> dict:
    if plan is None:
        return {}
    return dict(th=plan.th, tw=plan.tw, tcin=plan.tcin, tcout=plan.tcout)


# ---------------------------------------------------------------------------
# Stride-1 VALID conv (generic kernel)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("th", "tw", "tcin", "tcout",
                                             "pad", "out_start",
                                             "out_size"))
def _sd_conv2d_valid_jit(x: jax.Array, w: jax.Array, th: int, tw: int,
                         tcin: int, tcout: int,
                         pad: Tuple[PadPair, PadPair],
                         out_start: Tuple[int, int],
                         out_size: Optional[Tuple[int, int]]) -> jax.Array:
    return _k.sd_conv_pallas(x, w, th=th, tw=tw, tcin=tcin, tcout=tcout,
                             pad=pad, out_start=out_start,
                             out_size=out_size, interpret=not _on_tpu())


def sd_conv2d_valid(x: jax.Array, w: jax.Array, th: int | None = None,
                    tcin: int | None = None, tcout: int | None = None,
                    tw: int | None = None,
                    pad: Tuple[PadPair, PadPair] = ((0, 0), (0, 0)),
                    out_start: Tuple[int, int] = (0, 0),
                    out_size: Optional[Tuple[int, int]] = None
                    ) -> jax.Array:
    """Stride-1 conv (B,H,W,Cin)x(KTh,KTw,Cin,Co) via the Pallas kernel.

    ``pad`` is zero padding (one padded copy per launch);
    ``out_start``/``out_size`` select a contiguous
    output window so downstream crops fold into the launch.  The plan
    lookup happens OUTSIDE jit so the jit cache is keyed on the resolved
    tiles — plans tuned later in the process take effect on the next
    call instead of being baked in at first trace.
    """
    b, h, wd, cin = x.shape
    kth, ktw, _, cout = w.shape
    (plo_h, phi_h), (plo_w, phi_w) = pad
    geom = ConvGeom(b, h + plo_h + phi_h, wd + plo_w + phi_w, cin, cout,
                    kth, 1, ktw=0 if ktw == kth else ktw,
                    dtype="int8" if _k._is_int8_pair(x, w) else "")
    plan = _resolve_plan(geom, th, tcin, tcout, tw)
    return _sd_conv2d_valid_jit(x, w, plan.th, plan.tw, plan.tcin,
                                plan.tcout, pad, out_start, out_size)


def ws_to_ocmajor(ws: jax.Array, s: int) -> jax.Array:
    """Relayout split filters from n-major (core) to oc-major (kernel).

    Canonical implementation lives in :mod:`repro.sd.plan` (the plan
    layer owns filter layouts now); re-exported here for the kernel
    benchmarks and tests that predate ``repro.sd``.
    """
    from repro.sd.plan import to_ocmajor
    return to_ocmajor(ws, s)


# ---------------------------------------------------------------------------
# Fused conv + interleave (+ epilogue)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit,
                   static_argnames=("s", "act", "th", "tw", "tcin",
                                    "tcout", "pad", "crop", "out_space",
                                    "out_dtype"))
def _sd_fused_jit(x: jax.Array, ws_ocmajor: jax.Array, s,
                  bias: jax.Array | None, act: str, th: int, tw: int,
                  tcin: int, tcout: int, pad, crop,
                  out_space, scale: jax.Array | None = None,
                  out_dtype: str | None = None) -> jax.Array:
    return _k.sd_fused_pallas(x, ws_ocmajor, s, bias=bias, act=act,
                              scale=scale,
                              th=th, tw=tw, tcin=tcin, tcout=tcout,
                              pad=pad, crop=crop, out_space=out_space,
                              out_dtype=out_dtype,
                              interpret=not _on_tpu())


def sd_deconv_fused(x: jax.Array, ws_ocmajor: jax.Array, s,
                    bias: jax.Array | None = None, act: str = "linear",
                    th: int | None = None, tcin: int | None = None,
                    tcout: int | None = None,
                    tw: int | None = None) -> jax.Array:
    """Fused split-conv + interleave on an *already padded* input,
    returning the *uncropped* interleaved output — the pre-zero-copy
    contract, kept for the reference path and the kernel unit tests.
    ``s`` is an int (square 2-D) or an ``(sh, sw)`` pair (the 1-D
    lowering).  Plan lookup is outside jit (see sd_conv2d_valid).
    """
    sh, sw = (s, s) if isinstance(s, int) else (int(s[0]), int(s[1]))
    b, h, wd, cin = x.shape
    kth, ktw = ws_ocmajor.shape[0], ws_ocmajor.shape[1]
    cout = ws_ocmajor.shape[-1] // (sh * sw)
    plan = _resolve_plan(ConvGeom(b, h, wd, cin, cout, kth, sh,
                                  ktw=0 if ktw == kth else ktw,
                                  sw=0 if sw == sh else sw),
                         th, tcin, tcout, tw)
    return _sd_fused_jit(x, ws_ocmajor, s, bias, act, plan.th, plan.tw,
                         plan.tcin, plan.tcout, ((0, 0), (0, 0)), (0, 0),
                         None)


def sd_deconv_presplit_fused(x: jax.Array, ws_ocmajor: jax.Array,
                             kernel, stride, padding=0, *,
                             output_padding=0,
                             bias: jax.Array | None = None,
                             act: str = "linear",
                             scale: jax.Array | None = None,
                             out_dtype=None,
                             plan: KernelPlan | None = None,
                             zero_copy: bool = True) -> jax.Array:
    """2-D transposed conv from *pre-split* oc-major filters via the
    fused Pallas kernel.

    The zero-copy default writes the output once: the ``P_K`` +
    user-padding crop is the phase-offset epilogue writing final output
    geometry, and ``output_padding`` rows past the shuffled support come
    out of the kernel as ``act(bias)`` (their input windows are zero
    padding) — no out-of-kernel extend fallback.  ``zero_copy=False`` is the old
    pad -> kernel -> crop composition, kept as the parity/traffic
    reference.

    This is the engine's hot path (`repro.engine`): ``ws_ocmajor`` (with
    folded BN scale), ``bias`` and ``plan`` come from the per-layer plan
    cache, so nothing here touches ``split_filters``.

    Int8 launches (int8 ``x`` and ``ws_ocmajor``, with the combined
    dequant ``scale`` — (B, Cout*ss) dynamic or (1, Cout*ss) static)
    require the zero-copy path: the pad -> kernel -> crop reference has
    no in-kernel dequant epilogue.  ``out_dtype="int8"`` (chained
    launches) makes the epilogue re-quantize in VMEM so the output
    tensor lands in HBM as int8; the autotune key then carries
    ``_q8out`` (the output tile is 4x smaller in VMEM).
    """
    s = _ntuple(stride, 2)
    op = _ntuple(output_padding, 2)
    kh, kw = kernel
    _check_padding((kh, kw), padding)
    _check_output_padding(op, s)
    pads = _pads(padding)
    (kth, ktw), pk, (pih, piw) = sd_geometry((kh, kw), s)
    out_space = deconv_output_shape(x.shape[1:3], (kh, kw), s, padding,
                                    output_padding)
    sarg = s[0] if s[0] == s[1] else s
    quant = _k._is_int8_pair(x, ws_ocmajor)
    if quant and not zero_copy:
        raise ValueError("int8 presplit execution requires the "
                         "zero-copy fused path (the reference "
                         "composition has no dequant epilogue)")
    qout = out_dtype is not None and jnp.dtype(out_dtype) == jnp.int8
    if zero_copy:
        b, h, wd, cin = x.shape
        cout = ws_ocmajor.shape[-1] // (s[0] * s[1])
        if any(o == 0 for o in out_space):
            # Degenerate geometry (a zero-extent output dim passes
            # padding validation): nothing to launch — match the
            # pad->kernel->crop reference, which crops to empty.
            dt = out_dtype if out_dtype is not None else (
                jnp.float32 if quant else x.dtype)
            return jnp.zeros((b, *out_space, cout), dt)
        crop = tuple(pki + lo for pki, (lo, _) in zip(pk, pads))
        rplan = plan if plan is not None else _resolve_plan(
            ConvGeom(b, h + 2 * pih, wd + 2 * piw, cin, cout, kth, s[0],
                     ktw=0 if ktw == kth else ktw,
                     sw=0 if s[1] == s[0] else s[1],
                     out_h=out_space[0], out_w=out_space[1],
                     crop_h=crop[0], crop_w=crop[1],
                     dtype="int8" if quant else "", qout=qout),
            None, None, None)
        return _sd_fused_jit(x, ws_ocmajor, sarg, bias, act, rplan.th,
                             rplan.tw, rplan.tcin, rplan.tcout,
                             ((pih, pih), (piw, piw)), crop,
                             tuple(out_space), scale,
                             "int8" if qout else None)

    # ---- reference composition: pad -> uncropped kernel -> crop ------
    xp = jnp.pad(x, ((0, 0), (pih, pih), (piw, piw), (0, 0)))
    kw_args = _plan_kwargs(plan)
    # When output_padding reaches past the shuffled support (op > high
    # crop), crop_interleaved zero-extends AFTER the kernel — so the
    # in-kernel bias/act epilogue would be missing on those rows.  Run
    # the epilogue outside the kernel in that (rare) case; the common
    # case keeps the fully fused epilogue.
    extend = any(opi > hi for (_, hi), opi in zip(pads, op))
    if not extend:
        full = sd_deconv_fused(xp, ws_ocmajor, sarg, bias=bias, act=act,
                               **kw_args)
        return crop_interleaved(full, pk, pads, out_space)
    full = sd_deconv_fused(xp, ws_ocmajor, sarg, **kw_args)
    out = crop_interleaved(full, pk, pads, out_space)
    out = out.astype(jnp.float32)
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    if act == "relu":
        out = jax.nn.relu(out)
    elif act == "tanh":
        out = jnp.tanh(out)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Winograd fast-algorithm path (F(2,r) on the stride-1 subfilters)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit,
                   static_argnames=("kt", "s", "act", "th", "tw", "tcin",
                                    "tcout", "pad", "crop", "out_space"))
def _sd_wino_jit(x: jax.Array, u: jax.Array, kt, s,
                 bias: jax.Array | None, act: str, th: int, tw: int,
                 tcin: int, tcout: int, pad, crop,
                 out_space) -> jax.Array:
    return _wk.sd_wino_pallas(x, u, kt, s, bias=bias, act=act,
                              th=th, tw=tw, tcin=tcin, tcout=tcout,
                              pad=pad, crop=crop, out_space=out_space,
                              interpret=not _on_tpu())


def sd_deconv_presplit_wino(x: jax.Array, u: jax.Array,
                            kernel, stride, padding=0, *,
                            output_padding=0,
                            bias: jax.Array | None = None,
                            act: str = "linear",
                            plan: KernelPlan | None = None) -> jax.Array:
    """2-D transposed conv from *pre-transformed* Winograd filters via
    the fused fast-algorithm Pallas kernel.

    ``u`` is the oc-major split filter stack already passed through the
    F(2,r) filter transform (``plan.bind`` on a winograd plan, or
    :func:`repro.kernels.winograd.transform_filters`): shape
    ``(alpha_h, alpha_w, Cin, Cout*prod(s))``.  Same contract as
    :func:`sd_deconv_presplit_fused` — the ``P_I`` pad is the launch's
    zero pad, the ``P_K`` + user crop and the inverse output transform are
    folded into the epilogue together with bias/act/interleave.  Float
    only (no int8 path); the autotune plan cache keys these launches
    under ``algo="wino"`` so direct and Winograd tiles never collide.
    """
    s = _ntuple(stride, 2)
    op = _ntuple(output_padding, 2)
    kh, kw = kernel
    _check_padding((kh, kw), padding)
    _check_output_padding(op, s)
    pads = _pads(padding)
    (kth, ktw), pk, (pih, piw) = sd_geometry((kh, kw), s)
    out_space = deconv_output_shape(x.shape[1:3], (kh, kw), s, padding,
                                    output_padding)
    sarg = s[0] if s[0] == s[1] else s
    b, h, wd, cin = x.shape
    cout = u.shape[-1] // (s[0] * s[1])
    if any(o == 0 for o in out_space):
        return jnp.zeros((b, *out_space, cout), x.dtype)
    crop = tuple(pki + lo for pki, (lo, _) in zip(pk, pads))
    rplan = plan if plan is not None else _resolve_plan(
        ConvGeom(b, h + 2 * pih, wd + 2 * piw, cin, cout, kth, s[0],
                 ktw=0 if ktw == kth else ktw,
                 sw=0 if s[1] == s[0] else s[1],
                 out_h=out_space[0], out_w=out_space[1],
                 crop_h=crop[0], crop_w=crop[1], algo="wino"),
        None, None, None)
    return _sd_wino_jit(x, u, (kth, ktw), sarg, bias, act, rplan.th,
                        rplan.tw, rplan.tcin, rplan.tcout,
                        ((pih, pih), (piw, piw)), crop,
                        tuple(out_space))


def sd_deconv_presplit_wino_1d(x: jax.Array, u: jax.Array,
                               kernel, stride, padding=0, *,
                               output_padding=0,
                               bias: jax.Array | None = None,
                               act: str = "linear",
                               plan: KernelPlan | None = None
                               ) -> jax.Array:
    """1-D Winograd SD, lowered as H=1 2-D (mirrors
    :func:`sd_deconv_presplit_fused_1d`): x (B, L, Cin), u the
    transformed filters ``(alpha, Cin, Cout*s)`` — the unit H axis gets
    the degenerate F(1,1) transform (alpha_h = 1), so no MACs are
    wasted on it."""
    (k,) = _ntuple(kernel, 1)
    (s,) = _ntuple(stride, 1)
    ((lo, hi),) = _pads_nd(padding, 1)
    (op,) = _ntuple(output_padding, 1)
    y = sd_deconv_presplit_wino(
        x[:, None], u[None], (1, k), (1, s),
        ((0, 0), (lo, hi)), output_padding=(0, op), bias=bias, act=act,
        plan=plan)
    return y[:, 0]


# ---------------------------------------------------------------------------
# Rank lowerings: 1-D and 3-D SD through the same 2-D Pallas kernels.
# ---------------------------------------------------------------------------

def sd_deconv_presplit_fused_1d(x: jax.Array, ws_ocmajor: jax.Array,
                                kernel, stride, padding=0, *,
                                output_padding=0,
                                bias: jax.Array | None = None,
                                act: str = "linear",
                                scale: jax.Array | None = None,
                                out_dtype=None,
                                plan: KernelPlan | None = None
                                ) -> jax.Array:
    """1-D SD through the fused kernel, lowered as H=1 2-D.

    x: (B, L, Cin); ws_ocmajor: (KT, Cin, Cout*s) with channel
    c = oc*s + phase.  The length axis becomes the kernel's width axis
    (a (1, KT) filter, interleave (1, s)) — same kernel, no wasted MACs,
    and the pad/crop folding applies to the length axis via the
    kernel's width machinery.  ``scale`` (int8): (B, Cout*s),
    oc-major — the (1, s) lowering keeps the phase-channel order.
    """
    (k,) = _ntuple(kernel, 1)
    (s,) = _ntuple(stride, 1)
    ((lo, hi),) = _pads_nd(padding, 1)
    (op,) = _ntuple(output_padding, 1)
    y = sd_deconv_presplit_fused(
        x[:, None], ws_ocmajor[None], (1, k), (1, s),
        ((0, 0), (lo, hi)), output_padding=(0, op), bias=bias, act=act,
        scale=scale, out_dtype=out_dtype, plan=plan)
    return y[:, 0]


def sd_deconv_presplit_fused_3d(x: jax.Array, ws_nmajor: jax.Array,
                                kernel, stride, padding=0, *,
                                output_padding=0,
                                bias: jax.Array | None = None,
                                act: str = "linear",
                                scale: jax.Array | None = None,
                                out_dtype=None,
                                plan: KernelPlan | None = None
                                ) -> jax.Array:
    """3-D SD: depth folded into batch for the intra-slice convs.

    x: (B, D, H, W, Cin); ws_nmajor: (KT_d, KT_h, KT_w, Cin, N*Cout)
    n-major (N = s_d*s_h*s_w).  Each depth tap ``td`` of the split
    stride-1 conv is an *intra-slice* 2-D conv applied to a shifted band
    of depth slices — so each tap runs through the 2-D Pallas conv
    kernel with (B * D_out) as the batch axis and the H/W ``P_I`` pads
    applied by its launch (the depth pad is materialised here, to slice
    the tap bands from); the cross-slice coupling is a plain f32
    accumulation over the KT_d taps, and the 3-D interleave + bias/act
    epilogue falls back to grouped-XLA layout ops (``depth_to_space``).
    No new kernels.

    Int8 (int8 ``x``/``ws_nmajor`` with an n-major (B, N*Cout)
    ``scale``): each tap conv returns exact int32 partial sums, the
    tap accumulation stays int32, and the combined dequant scale is
    applied per (sample, n-major phase channel) *before* the 3-D
    interleave; output f32.
    """
    s = _ntuple(stride, 3)
    k = _ntuple(kernel, 3)
    pads = _pads_nd(padding, 3)
    op = _ntuple(output_padding, 3)
    _check_padding(k, padding)
    _check_output_padding(op, s)
    (ktd, kth, ktw), pk, pi = sd_geometry(k, s)
    out_space = deconv_output_shape(x.shape[1:4], k, s, padding,
                                    output_padding)
    xp = jnp.pad(x, ((0, 0), (pi[0], pi[0]), (0, 0), (0, 0), (0, 0)))
    b, dp, h, wd, cin = xp.shape
    od = dp - ktd + 1
    oh1, ow1 = h + 2 * pi[1] - kth + 1, wd + 2 * pi[2] - ktw + 1
    nco = ws_nmajor.shape[-1]
    tile = dict(th=plan.th, tw=plan.tw, tcin=plan.tcin,
                tcout=plan.tcout) if plan is not None else {}
    hw_pad = ((pi[1], pi[1]), (pi[2], pi[2]))
    quant = _k._is_int8_pair(x, ws_nmajor)
    acc = None
    for td in range(ktd):
        xs = jax.lax.slice_in_dim(xp, td, td + od, axis=1)
        xs = xs.reshape(b * od, h, wd, cin)
        y2 = sd_conv2d_valid(xs, ws_nmajor[td], pad=hw_pad, **tile)
        if not quant:                    # int8 taps stay exact int32
            y2 = y2.astype(jnp.float32)
        acc = y2 if acc is None else acc + y2
    y = acc.reshape(b, od, oh1, ow1, nco)
    if quant:
        if scale is None:
            scale = jnp.ones((b, nco), jnp.float32)
        # Dequant before the interleave: n-major phase channels carry
        # distinct scales (per-sample activation x per-channel filter;
        # a single static row broadcasts over the batch).
        y = y.astype(jnp.float32) * scale.astype(jnp.float32).reshape(
            -1, 1, 1, 1, nco)
    full = depth_to_space(y, s)
    out = crop_interleaved(full, pk, pads, out_space)
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    if act == "relu":
        out = jax.nn.relu(out)
    elif act == "tanh":
        out = jnp.tanh(out)
    if quant and out_dtype is not None and jnp.dtype(out_dtype) == jnp.int8:
        # Chained launch: 1/sx_next is already folded into scale+bias —
        # re-quantize with the same round + saturating clamp as the
        # fused kernel's epilogue.
        return jnp.clip(jnp.round(out), -127.0, 127.0).astype(jnp.int8)
    return out.astype(jnp.float32 if quant else x.dtype)


def sd_deconv_kernel(x: jax.Array, w: jax.Array, stride: int,
                     padding=0, *, bias: jax.Array | None = None,
                     act: str = "linear",
                     plan: KernelPlan | None = None,
                     zero_copy: bool = True) -> jax.Array:
    """Full SD transposed conv through the fused Pallas kernel.

    Drop-in replacement for core.sd_deconv (same semantics), with the
    paper's stride-s write performed inside the kernel.  Splits filters
    inline — deployments should pre-split once and call
    :func:`sd_deconv_presplit_fused` (see ``repro.engine``).
    """
    s = int(stride)
    ws = ws_to_ocmajor(split_filters(w, s), s)
    return sd_deconv_presplit_fused(x, ws, w.shape[:2], s, padding,
                                    bias=bias, act=act, plan=plan,
                                    zero_copy=zero_copy)


# ---------------------------------------------------------------------------
# Backward convolutions (the SD training path, see repro.sd.grad)
# ---------------------------------------------------------------------------

def sd_input_grad_fused(dy1: jax.Array, ws: jax.Array,
                        pi: Tuple[int, int],
                        space: Tuple[int, int],
                        plan: KernelPlan | None = None) -> jax.Array:
    """VJP of ``y1 = conv_valid_stride1(pad(x, P_I), ws)`` w.r.t. ``x``,
    on the Pallas kernel: a FULL stride-1 conv of ``dy1`` with the
    rot180, channel-swapped split filters, expressed as a padded
    VALID conv — the ``(K_T - 1)`` FULL-conv pad is the launch's zero
    pad, and the trailing ``P_I`` crop (the pad^T of the forward) is
    folded into the launch as an output window, so ``dx`` is written
    directly at final geometry.

    dy1: (B, O1h, O1w, N*Co); ws: split filters (KTh, KTw, Cin, N*Co);
    returns dx: (B, *space, Cin).
    """
    kth, ktw = ws.shape[0], ws.shape[1]
    w_t = jnp.swapaxes(ws[::-1, ::-1], -1, -2)     # rot180, swap ic/oc
    b, o1h, o1w, nco = dy1.shape
    cin = ws.shape[2]
    geom = ConvGeom(b, o1h + 2 * (kth - 1), o1w + 2 * (ktw - 1), nco,
                    cin, kth, 1, ktw=0 if ktw == kth else ktw, tag="dx")
    rplan = plan or autotune.get_plan(geom)
    return _sd_conv2d_valid_jit(
        dy1, w_t, rplan.th, rplan.tw, rplan.tcin, rplan.tcout,
        ((kth - 1, kth - 1), (ktw - 1, ktw - 1)), tuple(pi),
        tuple(space))


def _dw_tiles(o1: Tuple[int, int], wp: int, kt: Tuple[int, int],
              cin: int, nco: int) -> Tuple[int, int, int]:
    """``(tr, tcin, tcout)`` of the filter-grad kernel: full channel
    depth and all ``O1h`` rows, shrunk until its *actual* per-step
    footprint fits VMEM — a band of ``tr + KTh - 1`` padded input rows
    at the full padded width ``wp``, ``tr`` rows of ``dy1``, the per-tap
    accumulator and the output tile.  The generic conv-band model the
    autotuner's heuristic uses does not describe this kernel.  Channels
    shrink first, in 128-lane steps, then the row band."""
    (o1h, o1w), (kth, ktw) = o1, kt
    wp8 = -(-wp // 8) * 8

    def nbytes(tr: int, ci: int, co: int) -> int:
        return 4 * ((tr + kth - 1) * wp8 * ci + tr * o1w * co
                    + 2 * kth * ktw * ci * co)

    tr, tcin, tcout = o1h, cin, nco
    while nbytes(tr, tcin, tcout) > autotune.VMEM_BUDGET:
        ci = autotune.smaller_lane_tile(cin, tcin)
        co = autotune.smaller_lane_tile(nco, tcout)
        if ci and (tcin >= tcout or not co):
            tcin = ci
        elif co:
            tcout = co
        elif tr > 1:
            tr = -(-tr // 2)
        else:
            break
    return tr, tcin, tcout


def sd_filter_grad_fused(x: jax.Array, dy1: jax.Array,
                         kt: Tuple[int, int], pi: Tuple[int, int],
                         plan: KernelPlan | None = None) -> jax.Array:
    """VJP of ``y1 = conv_valid_stride1(pad(x, P_I), ws)`` w.r.t. ``ws``
    on the Pallas filter-grad kernel: the batch/channel-exchanged VALID
    conv, with the ``P_I`` activation pad applied by the launch.

    x: (B, H, W, Cin) *unpadded*; dy1: (B, O1h, O1w, N*Co);
    returns dws: (KTh, KTw, Cin, N*Co).  Unpinned tiles come from this
    kernel's own VMEM footprint (see _dw_tiles); an explicitly pinned
    ``plan``'s channel tiles are trusted as-is.
    """
    _, h, wd, cin = x.shape
    _, o1h, o1w, nco = dy1.shape
    if plan is not None:
        tr, tcin, tcout = o1h, plan.tcin, plan.tcout
    else:
        tr, tcin, tcout = _dw_tiles((o1h, o1w), wd + 2 * pi[1], kt, cin,
                                    nco)
    return _sd_filter_grad_jit(x, dy1, kt, tuple((p, p) for p in pi),
                               tr, tcin, tcout)


@functools.partial(jax.jit, static_argnames=("kt", "pad", "tr", "tcin",
                                             "tcout"))
def _sd_filter_grad_jit(x: jax.Array, dy1: jax.Array, kt, pad, tr: int,
                        tcin: int, tcout: int) -> jax.Array:
    return _k.sd_filter_grad_pallas(x, dy1, kt, pad=pad, tr=tr, tcin=tcin,
                                    tcout=tcout,
                                    interpret=not _on_tpu())
