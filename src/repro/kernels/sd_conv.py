"""Pallas TPU kernels for Split Deconvolution.

Three kernels:

* ``sd_conv_kernel``        — stride-1 VALID convolution with the stacked
  split filters (the grouped-GEMM view of SD).  Generic small-K conv
  kernel with zero padding and an optional contiguous output window, so
  FULL convs and cropped outputs never materialise uncropped copies in
  HBM.
* ``sd_fused_kernel``       — the same convolution, but each block *also*
  performs the paper's stride-``s`` output write: the bias + activation
  epilogue runs on the accumulated phases, and strided VMEM stores
  interleave them into the deconv output tile.  Narrow Cout tiles
  (under one 128-lane tile, :func:`phase_packed`) pack the ``s^2``
  phases side by side in the matmul's lanes; wider ones accumulate
  each phase on its own.  The ``P_K`` + user-padding crop is folded
  into the write (phase-offset stores + trimmed ``out_shape``) — the
  tile leaves VMEM in final output geometry.
* ``sd_filter_grad_kernel`` — the filter-gradient VALID conv of the SD
  backward (``dw[t] = sum_{b,v} xpad[b, v+t] dy1[b, v]``): one MXU GEMM
  per tap over a band of ``dy1`` rows, with batch and row bands as the
  accumulation axes.

TPU mapping (see DESIGN.md "Memory traffic"):
  - the ``P_I`` halo is a zero pad applied by the wrapper (``_window``,
    one XLA pad per launch).  Mosaic accepts element windows only inside
    the array, so the bands read no out-of-range rows and the kernels
    need no border masks.  Input bands are bound with element-indexed
    block dims (``pl.Element``): the index map returns element offsets,
    which is what lets consecutive bands overlap by the (K_T - 1) halo.
  - the conv columns a step computes round up to the operand's sublane
    tile (8 rows of f32), the band width to a multiple of 8; the extra
    columns are zero padding and feed no written output.
  - grid = (batch, out-row-tiles, out-col-tiles, cout-tiles, cin-tiles)
    with the input-channel (reduction) axis innermost and marked
    ``arbitrary``; the four outer axes are ``parallel``.  Row/col grids
    ceil-divide the output — trailing partial blocks are Pallas-managed.
  - each step loads an input band with a (K_T - 1) halo per spatial dim
    and issues K_Th*K_Tw MXU matmuls of shape (rows*cols, TCin) x
    (TCin, N): N = TCout in the conv kernel; in the fused kernel N =
    s^2*TCout once per tap when the phases are packed, else N = TCout
    once per tap and phase.  A matmul and the accumulator pad N to
    whole 128-lane tiles, which is what packing narrow tiles saves.
  - partial sums live in a VMEM scratch accumulator that persists across
    the Cin-tile grid steps; the output block is written exactly once, by
    the epilogue at the last Cin tile.
  - channel tiles are the full depth or a multiple of 128 lanes (see
    ``autotune.lane_tiles``); the interleave uses strided stores, which
    Mosaic lowers for 32-bit data only, so the fused epilogue assembles
    the tile in an f32 scratch and casts it once on the way out.

Validated in interpret mode against ``ref.py`` (tests/test_kernels.py,
tests/test_zero_copy.py) and compiled for TPU v5e by
tests/test_tpu_compile.py.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import jax.experimental.pallas as pl
from jax.experimental.pallas import tpu as pltpu

PadPair = Tuple[int, int]


def _compiler_params(n_parallel: int, n_arbitrary: int):
    return pltpu.CompilerParams(dimension_semantics=(
        ("parallel",) * n_parallel + ("arbitrary",) * n_arbitrary))


def _apply_act(y: jax.Array, act: str) -> jax.Array:
    if act == "linear":
        return y
    if act == "relu":
        return jnp.maximum(y, 0.0)
    if act == "tanh":
        return jnp.tanh(y)
    raise ValueError(f"unknown act {act!r}")


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


def _round8(n: int) -> int:
    return _cdiv(n, 8) * 8


def _round_sublanes(n: int, dtype) -> int:
    """``n`` rounded up to the sublane tile of ``dtype``: 8 rows of
    32-bit data, 16 of bf16, 32 of int8.  Band columns computed at that
    width keep every (rows, cols, C) <-> (rows*cols, C) reshape a pure
    retiling."""
    tile = 8 * 4 // jnp.dtype(dtype).itemsize
    return _cdiv(n, tile) * tile


# Pixels one inner-loop step processes.  Mosaic unrolls a kernel body's
# vector ops over their (8, 128) tiles, so a body that works on a whole
# (rows x cols) tile at once compiles in minutes at MDE's widths; the
# row-chunk loops keep the unrolled code, and the compile time, bounded.
_CHUNK_PIXELS = 512


def _row_chunk(rows: int, cols: int) -> int:
    """Rows per loop step: the largest divisor of ``rows`` whose
    (chunk x cols) slab stays within ``_CHUNK_PIXELS`` (at least 1)."""
    return max(r for r in range(1, rows + 1)
               if rows % r == 0 and (r == 1 or r * cols <= _CHUNK_PIXELS))


def _for_row_chunks(rows: int, cols: int, step) -> None:
    """Run ``step(r, n)`` over row chunks ``[r, r + n)`` of ``rows``."""
    n = _row_chunk(rows, cols)

    def body(c, carry):
        step(c * n, n)
        return carry

    jax.lax.fori_loop(0, rows // n, body, 0)


def _window(x: jax.Array, lo: Tuple[int, int],
            size: Tuple[int, int]) -> jax.Array:
    """Rows ``[-lo[0], size[0] - lo[0])`` x cols ``[-lo[1], ...)`` of
    ``x`` (B, H, W, C), zero outside ``x``.  A negative ``lo`` (or a
    negative implied high side) crops instead of padding."""
    _, h, w, _ = x.shape
    cfg = ((0, 0, 0), (lo[0], size[0] - lo[0] - h, 0),
           (lo[1], size[1] - lo[1] - w, 0), (0, 0, 0))
    return jax.lax.pad(x, jnp.zeros((), x.dtype), cfg)


def _band_spec(band_h: int, band_w: int, tcin: int, row: int, col: int,
               nw: int, nci: int):
    """Element-indexed (1, band_h, band_w, tcin) input band of grid step
    (bi, i, j, co, ci): rows from ``i*row``, cols from ``j*col``.  A
    tiled (col, channel) dim with one grid step gets the literal offset
    0: Mosaic must prove the offset divides by the (8, 128) tiling."""
    def index(bi, i, j, co, ci):
        return (bi, i * row, j * col if nw > 1 else 0,
                ci * tcin if nci > 1 else 0)
    return pl.BlockSpec(
        (pl.Element(1), pl.Element(band_h), pl.Element(band_w),
         pl.Element(tcin)), index)


def _is_int8_pair(x: jax.Array, w: jax.Array) -> bool:
    return x.dtype == jnp.int8 and w.dtype == jnp.int8


def _dot(a: jax.Array, b: jax.Array, contract: int = 1) -> jax.Array:
    """One MXU matmul contracting ``a``'s dim ``contract`` with ``b``'s
    dim 0.  Int8 pairs run the MXU's 8-bit mode into exact int32; bf16
    pairs run natively into f32 (bf16 products are exact in f32);
    anything else runs in f32 at HIGHEST precision — the MXU's default
    f32 path rounds operands to bf16."""
    dims = (((contract,), (0,)), ((), ()))
    if _is_int8_pair(a, b):
        return jax.lax.dot_general(a, b, dims,
                                   preferred_element_type=jnp.int32)
    if a.dtype == b.dtype == jnp.bfloat16:
        return jax.lax.dot_general(a, b, dims,
                                   preferred_element_type=jnp.float32)
    return jax.lax.dot_general(a.astype(jnp.float32), b.astype(jnp.float32),
                               dims, precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def phase_packed(phases: int, tcout: int) -> bool:
    """Whether a fused launch packs its ``phases`` into the matmul's
    lanes: a Cout tile narrower than one 128-lane tile would otherwise
    pad every per-phase matmul and accumulator slab to 128 lanes."""
    return phases > 1 and tcout < 128


def _accumulate(x_ref, w_ref, acc_ref, *, kth: int, ktw: int, rows: int,
                cols: int) -> None:
    """``acc_ref[p, r] += sum_taps x[r + kh, kw : kw + cols] @ w[kh, kw, p]``
    for conv rows ``r < rows`` and every slab ``p``.

    x_ref: (1, band_h, band_w, TCin) band; w_ref: (KTh, KTw, P, TCin, N);
    acc_ref: (P, rows, cols, N).  Each tap's im2col slab is loaded once
    per row chunk and feeds one MXU matmul per slab: P phases of N =
    TCout, or one slab of N = s^2*TCout when the phases are packed."""
    tcin = x_ref.shape[-1]

    def step(r, n):
        for kh in range(kth):
            for kw in range(ktw):
                patch = x_ref[0, pl.ds(r + kh, n), kw:kw + cols, :]
                patch = patch.reshape(n * cols, tcin)
                for p in range(acc_ref.shape[0]):
                    acc_ref[p, pl.ds(r, n)] += _dot(
                        patch, w_ref[kh, kw, p]).reshape(n, cols, -1)

    _for_row_chunks(rows, cols, step)


# ---------------------------------------------------------------------------
# Generic stride-1 conv kernel (pad + output window)
# ---------------------------------------------------------------------------

def _sd_conv_body(x_ref, w_ref, o_ref, acc_ref, *, kth: int, ktw: int,
                  th: int, tw: int, cols: int):
    """One (batch, row-tile, col-tile, cout-tile, cin-tile) grid step."""
    ci = pl.program_id(4)

    @pl.when(ci == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    _accumulate(x_ref, w_ref, acc_ref, kth=kth, ktw=ktw, rows=th, cols=cols)

    @pl.when(ci == pl.num_programs(4) - 1)
    def _write():
        def step(r, n):
            o_ref[0, pl.ds(r, n)] = acc_ref[0, pl.ds(r, n), :tw].astype(
                o_ref.dtype)

        _for_row_chunks(th, cols, step)


def sd_conv_pallas(x: jax.Array, w: jax.Array, *, th: int = 8,
                   tw: int = 0, tcout: int | None = None,
                   tcin: int | None = None,
                   pad: Tuple[PadPair, PadPair] = ((0, 0), (0, 0)),
                   out_start: Tuple[int, int] = (0, 0),
                   out_size: Optional[Tuple[int, int]] = None,
                   interpret: bool) -> jax.Array:
    """Stride-1 VALID conv over the logically zero-padded input.

    x: (B, H, W, Cin); w: (KTh, KTw, Cin, Co) — rectangular filters
    allowed (the 1-D rank lowering runs a (1, KT) filter).  An int8
    (x, w) pair accumulates in int32 and returns the exact int32 conv
    (symmetric quantization: the zero padding is the int8 zero); the
    caller owns the dequant.

    ``pad`` is the logical zero padding; ``out_start`` / ``out_size``
    select a contiguous window of the conv output (in conv output ==
    padded-input coordinates), folding any downstream crop into the
    launch.  The wrapper pads (and crops) ``x`` once to exactly the rows
    and cols the grid reads.  ``tw == 0`` means no width tiling (one
    band spans the full output width).  Row/col grids ceil-divide the
    output; the trailing partial blocks are handled by Pallas.

    Output: (B, out_size[0], out_size[1], Co); defaults to the full conv
    output ``(H + pad - KT + 1)`` per dim.
    """
    b, h, wd, cin = x.shape
    kth, ktw, _, cout = w.shape
    (plo_h, phi_h), (plo_w, phi_w) = pad
    full_oh = h + plo_h + phi_h - kth + 1
    full_ow = wd + plo_w + phi_w - ktw + 1
    osh, osw = out_start
    oh, ow = out_size if out_size is not None else (full_oh, full_ow)
    th = min(th, oh)
    tw = min(tw or ow, ow)
    tcout = tcout or cout
    tcin = tcin or cin
    assert cout % tcout == 0 and cin % tcin == 0

    nh, nw = _cdiv(oh, th), _cdiv(ow, tw)
    cols = _round_sublanes(tw, x.dtype)
    band_h, band_w = th + kth - 1, _round8(cols + ktw - 1)
    # Output window row r reads padded rows from osh + r on.
    xw = _window(x, (plo_h - osh, plo_w - osw),
                 ((nh - 1) * th + band_h, (nw - 1) * tw + band_w))
    quant = _is_int8_pair(x, w)
    body = functools.partial(_sd_conv_body, kth=kth, ktw=ktw, th=th, tw=tw,
                             cols=cols)
    return pl.pallas_call(
        body,
        grid=(b, nh, nw, cout // tcout, cin // tcin),
        in_specs=[
            _band_spec(band_h, band_w, tcin, th, tw, nw, cin // tcin),
            pl.BlockSpec((kth, ktw, 1, tcin, tcout),
                         lambda bi, i, j, co, ci: (0, 0, 0, ci, co)),
        ],
        out_specs=pl.BlockSpec((1, th, tw, tcout),
                               lambda bi, i, j, co, ci: (bi, i, j, co)),
        out_shape=jax.ShapeDtypeStruct(
            (b, oh, ow, cout), jnp.int32 if quant else x.dtype),
        scratch_shapes=[pltpu.VMEM((1, th, cols, tcout),
                                   jnp.int32 if quant else jnp.float32)],
        compiler_params=_compiler_params(4, 1),
        interpret=interpret,
    )(xw, w[:, :, None])


# ---------------------------------------------------------------------------
# Fused conv + interleave + epilogue kernel (pad AND crop folded)
# ---------------------------------------------------------------------------

def _sd_fused_body(x_ref, w_ref, b_ref, *rest, kth: int, ktw: int,
                   rh: int, cols: int, th: int, tw: int, sh: int, sw: int,
                   res_h: int, res_w: int, out_h: int, out_w: int,
                   act: str, quant: bool, packed: bool):
    """Conv + epilogue + strided-store interleave.

    Unpacked (``tcout`` of 128 lanes or more): w_ref holds phase-major
    split filters, ``w_ref[kh, kw, p]`` the (TCin, TCout) matrix of
    phase ``p = py*sw + px``, so each phase accumulates in its own (rh,
    cols, TCout) slab of ``acc_ref``, and the epilogue adds the per-oc
    bias and applies the activation per phase.  Packed
    (:func:`phase_packed`): ``w_ref[kh, kw, 0]`` is one (TCin,
    ss*TCout) matrix, lane ``p*TCout + oc``, so one matmul per tap
    feeds one (rh, cols, ss*TCout) slab; the epilogue applies the
    packed (tiled) bias and the activation once over that slab, in
    place, and reads phase p back as lanes ``[p*TCout, (p+1)*TCout)``.

    The step computes ``rh`` conv rows (``th + 1`` when the residual
    crop ``res`` is nonzero) of ``cols >= tw (+ 1)`` conv columns.  The
    epilogue at the last cin tile stores phase (py, px) at rows
    ``py - res_h (mod sh)`` + k*sh and cols ``px - res_w (mod sw)`` +
    k*sw of the f32 tile ``y_ref`` — the interleave and the residual crop
    in one strided store — and writes the tile once, cast to the output
    dtype.

    ``quant``: int8 launch — the accumulator is exact int32 and a fourth
    operand carries the combined per-(sample, phase, oc) dequant scale
    (activation scale x folded per-channel filter scale), multiplied in
    before the bias.  A packed int8 launch keeps the activated f32
    values in the int32 accumulator as their bit patterns.
    """
    if quant:
        s_ref, o_ref, acc_ref, y_ref = rest
    else:
        o_ref, acc_ref, y_ref = rest
    ci = pl.program_id(4)

    @pl.when(ci == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    _accumulate(x_ref, w_ref, acc_ref, kth=kth, ktw=ktw, rows=rh,
                cols=cols)

    @pl.when(ci == pl.num_programs(4) - 1)
    def _epilogue():
        tc = y_ref.shape[-1]
        if packed:
            def activate(r, n):
                y = acc_ref[0, pl.ds(r, n)].astype(jnp.float32)
                if quant:
                    y = y * s_ref[0]
                y = _apply_act(y + b_ref[...], act)
                if quant:
                    y = jax.lax.bitcast_convert_type(y, jnp.int32)
                acc_ref[0, pl.ds(r, n)] = y

            _for_row_chunks(rh, cols, activate)
        for py in range(sh):
            for px in range(sw):
                p = py * sw + px
                # Phase rows land at py - res_h + k*sh; a negative start
                # means the phase's first conv row is cropped away.
                dy, dx = py - res_h, px - res_w
                r0, c0 = int(dy < 0), int(dx < 0)

                def phase(k, n, p=p, dy=dy, dx=dx, r0=r0, c0=c0):
                    if packed:
                        y = acc_ref[0, pl.ds(k + r0, n)][
                            ..., p * tc:(p + 1) * tc]
                        if quant:
                            y = jax.lax.bitcast_convert_type(y, jnp.float32)
                    else:
                        y = acc_ref[p, pl.ds(k + r0, n)].astype(jnp.float32)
                        if quant:
                            y = y * s_ref[0, p:p + 1, :]
                        y = _apply_act(y + b_ref[...], act)
                    y_ref[pl.ds(k * sh + dy % sh, n, stride=sh),
                          pl.ds(dx % sw, tw, stride=sw), :] = (
                              y[:, c0:c0 + tw])

                _for_row_chunks(th, cols, phase)

        def write(r, n):
            y = y_ref[pl.ds(r, n), :out_w]
            if o_ref.dtype == jnp.int8:
                # Chained launch: the next layer's 1/sx is already folded
                # into scale+bias, so re-quantizing is a round +
                # saturating clamp (never a wrapping cast) — the tile
                # leaves VMEM as the next layer's int8 input, f32 never
                # touches HBM.
                y = jnp.clip(jnp.round(y), -127.0, 127.0)
            o_ref[0, pl.ds(r, n)] = y.astype(o_ref.dtype)

        _for_row_chunks(out_h, tw * sw, write)


def sd_fused_pallas(x: jax.Array, ws_ocmajor: jax.Array, s, *,
                    bias: jax.Array | None = None, act: str = "linear",
                    scale: jax.Array | None = None,
                    th: int = 8, tw: int = 0, tcout: int | None = None,
                    tcin: int | None = None,
                    pad: Tuple[PadPair, PadPair] = ((0, 0), (0, 0)),
                    crop: Tuple[int, int] = (0, 0),
                    out_space: Optional[Tuple[int, int]] = None,
                    out_dtype=None,
                    interpret: bool) -> jax.Array:
    """Fused SD: split-filter conv + interleaved (pixel-shuffled) write.

    x:  (B, H, W, Cin) — the *unpadded* input; ``pad`` (the ``P_I``
        halo) is applied by the wrapper's one zero pad.
    ws_ocmajor: (KTh, KTw, Cin, Cout*sh*sw), channel c = oc*sh*sw + phase
        (the plan's stored layout); the wrapper relays it for the kernel
        — a per-call copy of the filters (see DESIGN.md "Memory
        traffic") — phase-major ``(KTh, KTw, sh*sw, Cin, Cout)``, or,
        for a phase-packed launch (:func:`phase_packed`), with the
        phases in the lanes of each Cout tile: ``(KTh, KTw, Cin,
        Cout/TCout, sh*sw, TCout)``, the core's n-major order when the
        tile is the whole Cout.
    s:  interleave factor — an int (square, the 2-D path) or an
        ``(sh, sw)`` pair (the 1-D lowering passes ``(1, s)``).
    bias: (Cout,) added per output channel in the epilogue (folded-BN
          beta); ``act`` in {"linear", "relu", "tanh"} applied after.
    scale: int8 launches only — f32 combined dequant scale per oc-major
          phase channel, either (B, Cout*sh*sw) (dynamic per-sample
          activation scales) or (1, Cout*sh*sw) (one *static*
          calibrated row shared by every sample).  Staged once per
          (batch, cout-tile) — the static row binds with a
          batch-independent index map — and multiplied into the int32
          accumulator in the epilogue, before bias/act.
    crop: low-side crop per dim in interleaved coordinates (``P_K`` +
          user padding); folded into the launch as a ``c // s`` input
          band offset plus a ``c % s`` phase offset of the stores.
    out_space: final output spatial shape (may extend past the shuffled
          support — ``output_padding`` rows read zero padding and come
          out as ``act(bias)``, matching the zero-extension semantics).
          Defaults to the uncropped interleave ``s * (H + pad - KT + 1)``.

    returns (B, *out_space, Cout) — final deconv output geometry, one
    HBM write per element.  ``out_dtype`` defaults to ``x.dtype`` for
    float launches and f32 (the dequantized value) for int8 launches;
    an int8 ``out_dtype`` (int8 launches only) makes the epilogue
    re-quantize the activated tile in VMEM — round + saturating clamp
    to ±127 — so the inter-layer tensor lives in HBM as int8 (the
    caller must have folded ``1/sx_next`` into ``scale`` and ``bias``).
    """
    sh, sw = (s, s) if isinstance(s, int) else (int(s[0]), int(s[1]))
    ss = sh * sw
    b, h, wd, cin = x.shape
    kth, ktw = ws_ocmajor.shape[0], ws_ocmajor.shape[1]
    cout = ws_ocmajor.shape[-1] // ss
    quant = _is_int8_pair(x, ws_ocmajor)
    if quant and scale is None:
        scale = jnp.ones((b, cout * ss), jnp.float32)
    if not quant and scale is not None:
        raise ValueError("scale requires an int8 (x, ws) pair")
    if out_dtype is None:
        out_dtype = jnp.float32 if quant else x.dtype
    out_dtype = jnp.dtype(out_dtype)
    if out_dtype == jnp.int8 and not quant:
        raise ValueError("int8 out_dtype requires an int8 (x, ws) pair")
    (plo_h, phi_h), (plo_w, phi_w) = pad
    full_oh = h + plo_h + phi_h - kth + 1     # conv rows incl. pad
    full_ow = wd + plo_w + phi_w - ktw + 1
    oh, ow = (out_space if out_space is not None
              else (full_oh * sh, full_ow * sw))
    q_h, res_h = divmod(crop[0], sh)
    q_w, res_w = divmod(crop[1], sw)
    tcout = tcout or cout
    tcin = tcin or cin
    assert cout % tcout == 0 and cin % tcin == 0
    if bias is None:
        bias = jnp.zeros((cout,), jnp.float32)
    bias2d = bias.astype(jnp.float32).reshape(1, cout)

    th = min(th, _cdiv(oh, sh))
    tw = min(tw or _cdiv(ow, sw), _cdiv(ow, sw))
    nh, nw = _cdiv(oh, th * sh), _cdiv(ow, tw * sw)
    rh = th + (1 if res_h else 0)             # conv rows per step
    cols = _round_sublanes(tw + (1 if res_w else 0), x.dtype)
    band_h, band_w = rh + kth - 1, _round8(cols + ktw - 1)
    # The q whole-interleave-row crop shifts the band origin by q rows.
    xw = _window(x, (plo_h - q_h, plo_w - q_w),
                 ((nh - 1) * th + band_h, (nw - 1) * tw + band_w))
    # A single-grid-step dim's output block is the whole (possibly
    # odd-sized) dim; otherwise th*s | block offsets.
    out_h, out_w = min(th * sh, oh), min(tw * sw, ow)
    packed = phase_packed(ss, tcout)
    # Accumulator slabs and the lanes of each: ss phases of TCout, or
    # one slab of the ss phases packed side by side.
    slabs, lanes = (1, ss * tcout) if packed else (ss, tcout)

    def phase_lanes(a: jax.Array) -> jax.Array:
        """(..., Cout*ss) oc-major channels -> (..., slabs, lanes) in
        the launch's lane order: phase-major (..., ss, Cout), or packed
        (..., 1, Cout*ss) with lane ``co*ss*TCout + p*TCout + oc`` in
        Cout tile ``co``."""
        if not packed:
            return jnp.swapaxes(a.reshape(*a.shape[:-1], cout, ss), -1, -2)
        a = a.reshape(*a.shape[:-1], cout // tcout, tcout, ss)
        return jnp.swapaxes(a, -1, -2).reshape(*a.shape[:-3], 1, cout * ss)

    wk = jnp.moveaxis(phase_lanes(ws_ocmajor), -2, 2)
    if packed:
        # The epilogue adds the bias to the packed slab: one copy per
        # phase, in the filters' lane order.
        bias2d = jnp.tile(bias2d.reshape(-1, 1, tcout), (1, ss, 1))
        bias2d = bias2d.reshape(1, cout * ss)

    body = functools.partial(
        _sd_fused_body, kth=kth, ktw=ktw, rh=rh, cols=cols, th=th, tw=tw,
        sh=sh, sw=sw, res_h=res_h, res_w=res_w, out_h=out_h, out_w=out_w,
        act=act, quant=quant, packed=packed)
    in_specs = [
        _band_spec(band_h, band_w, tcin, th, tw, nw, cin // tcin),
        pl.BlockSpec((kth, ktw, slabs, tcin, lanes),
                     lambda bi, i, j, co, ci: (0, 0, 0, ci, co)),
        pl.BlockSpec((1, lanes), lambda bi, i, j, co, ci: (0, co)),
    ]
    operands = [xw, wk, bias2d]
    if quant:
        # Dequant scales in the filters' lane order, (B|1, ss, Cout) or
        # packed (B|1, 1, Cout*ss): one (1, slabs, lanes) block staged
        # per (batch, cout-tile) grid step.  A single-row scale is the
        # *static* calibrated case — bind it with a batch-independent
        # index map so all samples share it.
        srows = phase_lanes(scale.astype(jnp.float32))
        if srows.shape[0] == 1:
            smap = lambda bi, i, j, co, ci: (0, 0, co)
        else:
            smap = lambda bi, i, j, co, ci: (bi, 0, co)
        in_specs.append(pl.BlockSpec((1, slabs, lanes), smap))
        operands.append(srows)
    return pl.pallas_call(
        body,
        grid=(b, nh, nw, cout // tcout, cin // tcin),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, out_h, out_w, tcout),
                               lambda bi, i, j, co, ci: (bi, i, j, co)),
        out_shape=jax.ShapeDtypeStruct((b, oh, ow, cout), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((slabs, rh, cols, lanes),
                       jnp.int32 if quant else jnp.float32),
            pltpu.VMEM((th * sh, tw * sw, tcout), jnp.float32)],
        compiler_params=_compiler_params(4, 1),
        interpret=interpret,
    )(*operands)


# ---------------------------------------------------------------------------
# Filter-gradient kernel (the SD backward's second stride-1 conv)
# ---------------------------------------------------------------------------

def _sd_filter_grad_body(x_ref, dy_ref, o_ref, acc_ref, *, kth: int,
                         ktw: int, tr: int, cols: int):
    """One (cin-tile, cout-tile, batch, row-band) grid step: per tap and
    row chunk one MXU GEMM ``(TCin, n*cols) x (n*cols, TCout)``,
    accumulated over the batch and the row bands."""
    first = (pl.program_id(2) == 0) & (pl.program_id(3) == 0)
    last = ((pl.program_id(2) == pl.num_programs(2) - 1)
            & (pl.program_id(3) == pl.num_programs(3) - 1))

    @pl.when(first)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    tcin, tco = x_ref.shape[-1], dy_ref.shape[-1]

    def step(r, n):
        dy = dy_ref[0, pl.ds(r, n)].reshape(n * cols, tco)
        for kh in range(kth):
            for kw in range(ktw):
                lhs = x_ref[0, pl.ds(r + kh, n), kw:kw + cols, :]
                acc_ref[kh, kw] += _dot(lhs.reshape(n * cols, tcin), dy,
                                        contract=0)

    _for_row_chunks(tr, cols, step)

    @pl.when(last)
    def _write():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def sd_filter_grad_pallas(x: jax.Array, dy1: jax.Array,
                          kt: Tuple[int, int], *,
                          pad: Tuple[PadPair, PadPair] = ((0, 0), (0, 0)),
                          tcout: int | None = None,
                          tcin: int | None = None,
                          tr: int | None = None,
                          interpret: bool) -> jax.Array:
    """VJP of ``y1 = conv_valid_stride1(pad(x), ws)`` w.r.t. ``ws``.

    x: (B, H, W, Cin) *unpadded* — the logical ``P_I`` pad is applied by
    the wrapper's one zero pad.  dy1: (B, O1h, O1w, NCo) cotangent of
    the split conv output, with O1 = H + pad - KT + 1 per dim.
    Returns dws: (KTh, KTw, Cin, NCo).

    The conv's taps are ``dy1``'s spatial extent (large), and its output
    extent is ``KT`` (tiny) — the roles are inverted vs the forward
    kernel, so each grid step runs one big GEMM per tap contracting over
    a band of ``tr`` rows of ``dy1`` (default: all ``O1h``), with an f32
    accumulator carried over the batch and row-band axes (innermost,
    ``arbitrary``).
    """
    b, h, wd, cin = x.shape
    kth, ktw = kt
    _, o1h, o1w, nco = dy1.shape
    (plo_h, phi_h), (plo_w, phi_w) = pad
    assert o1h == h + plo_h + phi_h - kth + 1, (o1h, h, pad, kth)
    assert o1w == wd + plo_w + phi_w - ktw + 1, (o1w, wd, pad, ktw)
    tcout = tcout or nco
    tcin = tcin or cin
    tr = min(tr or o1h, o1h)
    assert nco % tcout == 0 and cin % tcin == 0
    nr = _cdiv(o1h, tr)
    # Zero rows/cols past O1 (row bands that do not divide O1h, columns
    # rounded to the sublane tile) contribute nothing to the sums.
    cols = _round_sublanes(o1w, jnp.promote_types(x.dtype, dy1.dtype))
    xw = _window(x, (plo_h, plo_w), (nr * tr + kth - 1,
                                      _round8(cols + ktw - 1)))
    dyw = _window(dy1, (0, 0), (nr * tr, cols))
    body = functools.partial(_sd_filter_grad_body, kth=kth, ktw=ktw,
                             tr=tr, cols=cols)
    return pl.pallas_call(
        body,
        grid=(cin // tcin, nco // tcout, b, nr),
        in_specs=[
            pl.BlockSpec(
                (pl.Element(1), pl.Element(tr + kth - 1),
                 pl.Element(xw.shape[2]), pl.Element(tcin)),
                lambda ci, co, bi, r: (bi, r * tr, 0,
                                       ci * tcin if tcin < cin else 0)),
            pl.BlockSpec((1, tr, cols, tcout),
                         lambda ci, co, bi, r: (bi, r, 0, co)),
        ],
        out_specs=pl.BlockSpec((kth, ktw, tcin, tcout),
                               lambda ci, co, bi, r: (0, 0, ci, co)),
        out_shape=jax.ShapeDtypeStruct((kth, ktw, cin, nco), dy1.dtype),
        scratch_shapes=[pltpu.VMEM((kth, ktw, tcin, tcout), jnp.float32)],
        compiler_params=_compiler_params(2, 2),
        interpret=interpret,
    )(xw, dyw)
