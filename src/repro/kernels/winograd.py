"""Winograd fast-algorithm backend for the stride-1 split subfilters.

The paper's split transform turns every deconv into dense **stride-1**
convolutions of ``K_T = ceil(K/s)`` taps — exactly the shape Winograd
minimal-filtering algorithms accelerate (Chang et al., arXiv:1911.06918
apply the same observation to GAN deconvs).  F(2,3) computes a 2-wide
output tile of a 3-tap conv in 4 multiplies instead of 6 — 2.25x fewer
MACs in 2-D (16 vs 36 per 2x2 tile); F(2,2) saves 1.78x, F(2,5) 2.0x.

The algorithm is the classic three-transform identity

    Y = A^T [ (G g G^T) .x. (B^T d B) ] A          (2-D, per tile)

with per-dim matrices generated by the Toom-Cook construction below.
This module owns all three pieces and where they run:

* **filter transform** ``U = G g G^T`` — folded into ``plan.bind`` (see
  :meth:`repro.sd.plan.DeconvPlan.bind`), once offline per layer, like
  the split + BN fold.  A bound winograd plan's ``ws`` leaf holds the
  transformed filters (layout ``"wino"``); the hot path never sees
  ``G``.
* **input transform** ``V = B^T d B`` — inside the Pallas kernel, on the
  VMEM band, reusing the padded input window of
  :mod:`repro.kernels.sd_conv` (the band for ``nt`` output tiles spans
  ``nt*m + K_T - 1`` rows — the same halo shape as the direct kernel).
* **inverse output transform** ``Y = A^T M A`` — folded into the
  epilogue at the last cin tile, immediately before the existing
  stride-``s`` interleave + bias + act + crop.

Numerics: the transforms are exact in rational arithmetic but not in
f32 — evaluation points ``(0, +-1, +-2, +-1/2)`` keep the error small
(``~1e-6`` relative for F(2,3), growing with alpha).  ``WINO_TOL``
pins the accepted relative error per tap count; the registry records
the backend as ``exact=False`` with that tolerance and CI gates all 22
paper layers against it (plus an SSIM >= 0.999 end-to-end gate).

Supported geometry: spatial rank 1-2 (the 1-D lowering rides the same
H=1 trick as the direct kernel), per-dim ``K_T`` up to ``MAX_TAPS`` (5
-> F(2,5), alpha=6), float32/bfloat16.  Int8 is excluded: the
transformed-domain values are not representable in a per-channel int8
scale scheme (``plan()`` rejects the combination up front).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
import jax.experimental.pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .sd_conv import (PadPair, _apply_act, _band_spec, _cdiv,
                      _compiler_params, _window)

# Output tile size per dim.  m=2 is the sweet spot for the small K_T the
# split produces: larger m needs more evaluation points, whose spread is
# what amplifies f32 rounding.
OUTPUT_TILE = 2

# Largest per-dim tap count with acceptable f32 conditioning
# (K_T = 5 -> F(2,5), alpha = 6, points 0, +-1, +-2 + infinity).
MAX_TAPS = 5

# Toom-Cook evaluation points (plus the implicit point at infinity).
# Ordered so small alphas use the best-conditioned prefix.
_POINTS = (0.0, 1.0, -1.0, 2.0, -2.0, 0.5, -0.5)

# Pinned relative tolerance (vs the exact direct conv, scaled by
# max|ref|) per per-dim tap count — what the registry metadata, the
# parity tests and the CI gate all read.  Values hold with ~10x margin
# on the paper layers (see tests/test_winograd.py).
WINO_TOL = {1: 1e-6, 2: 1e-5, 3: 1e-5, 4: 1e-4, 5: 1e-4}


def tolerance(kt) -> float:
    """Pinned parity tolerance for a per-dim tap tuple (or int)."""
    taps = (kt,) if isinstance(kt, int) else tuple(kt)
    return max(WINO_TOL[min(int(t), MAX_TAPS)] for t in taps)


def output_tile(kt: int) -> int:
    """Per-dim output tile: degenerate 1-tap dims run F(1,1) = direct."""
    return 1 if kt == 1 else OUTPUT_TILE


def supported(kt, dtype: str = "native") -> bool:
    """Can the winograd backend execute split subfilters of per-dim tap
    counts ``kt`` (tuple; rank = len) at plan dtype ``dtype``?"""
    kt = tuple(kt)
    return (len(kt) <= 2 and dtype != "int8"
            and all(1 <= int(t) <= MAX_TAPS for t in kt))


@functools.lru_cache(maxsize=None)
def winograd_matrices(m: int, r: int) -> Tuple[np.ndarray, np.ndarray,
                                               np.ndarray]:
    """Toom-Cook matrices ``(A^T, G, B^T)`` for F(m, r): ``m`` correlation
    outputs of an ``r``-tap filter from ``alpha = m + r - 1`` inputs,

        y = A^T [ (G g) .x. (B^T d) ],    y[o] = sum_k d[o+k] g[k].

    Construction over points ``a_0..a_{alpha-2}`` plus infinity:
    ``G`` rows are the scaled Vandermonde rows ``a_i^k / N_i`` with
    ``N_i = prod_{j!=i}(a_i - a_j)`` (infinity row = ``e_{r-1}``),
    ``A^T`` columns are ``a_i^o`` (infinity column = ``e_{m-1}``), and
    ``B^T`` rows are the coefficients of ``M_i(x) = prod_{j!=i}(x-a_j)``
    (infinity row = coefficients of ``M(x)`` itself).  Exact in float64
    for the point set above; verified against the correlation identity
    by tests/test_winograd.py for every supported (m, r).
    """
    alpha = m + r - 1
    pts = np.asarray(_POINTS[:alpha - 1], np.float64)
    if alpha - 1 > len(_POINTS):
        raise ValueError(f"F({m},{r}): no point set for alpha={alpha}")

    at = np.zeros((m, alpha), np.float64)
    for i, a in enumerate(pts):
        at[:, i] = a ** np.arange(m)
    at[:, alpha - 1] = np.eye(m)[:, m - 1]        # infinity column

    g = np.zeros((alpha, r), np.float64)
    for i, a in enumerate(pts):
        n_i = np.prod(a - np.delete(pts, i)) if alpha > 2 else 1.0
        g[i] = (a ** np.arange(r)) / n_i
    g[alpha - 1, r - 1] = 1.0                     # infinity row

    bt = np.zeros((alpha, alpha), np.float64)
    for i in range(alpha - 1):
        # np.poly wants roots and returns decreasing powers; flip.
        # atleast_1d: an empty root list collapses to the scalar 1.0.
        coeffs = np.atleast_1d(np.poly(np.delete(pts, i)))[::-1]
        bt[i, :len(coeffs)] = coeffs
    bt[alpha - 1] = np.atleast_1d(np.poly(pts))[::-1]   # inf row: M(x)
    return (at.astype(np.float32), g.astype(np.float32),
            bt.astype(np.float32))


def transform_filters(ws: jax.Array,
                      kt: Optional[Tuple[int, ...]] = None) -> jax.Array:
    """The offline filter transform ``U = G g G^T`` per (cin, phase
    channel), applied tap-dim by tap-dim.

    ``ws``: oc-major split filters, rank 1 ``(KT, Cin, N*Cout)`` or
    rank 2 ``(KTh, KTw, Cin, N*Cout)``.  Returns the same layout with
    each tap dim expanded to ``alpha = m + KT - 1``.  Runs in f32 and
    casts back to the filter dtype (bf16 plans store bf16 transforms).
    """
    rank = ws.ndim - 2
    kt = tuple(int(t) for t in (kt or ws.shape[:rank]))
    if not supported(kt):
        raise ValueError(f"winograd: unsupported tap geometry {kt} "
                         f"(rank <= 2, per-dim K_T <= {MAX_TAPS})")
    u = ws.astype(jnp.float32)
    for d, taps in enumerate(kt):
        _, g, _ = winograd_matrices(output_tile(taps), taps)
        u = jnp.tensordot(jnp.asarray(g), u, axes=((1,), (d,)))
        u = jnp.moveaxis(u, 0, d)
    return u.astype(ws.dtype)


# ---------------------------------------------------------------------------
# Fused Winograd kernel: B^T d B -> elementwise MXU matmuls -> A^T M A ->
# interleave + bias + act + crop, all in one VMEM pass.
# ---------------------------------------------------------------------------

def _mat_rows(mat: np.ndarray, rows: list) -> list:
    """``out[i] = sum_j mat[i, j] * rows[j]`` with the matrix unrolled
    at trace time as Python scalars (Pallas bodies may not capture
    array constants).  The Toom-Cook matrices are mostly 0/+-1 — those
    entries cost an add or nothing, exactly the strength reduction the
    hand-written F(2,3) transforms perform."""
    out = []
    for i in range(mat.shape[0]):
        acc = None
        for j in range(mat.shape[1]):
            c = float(mat[i, j])
            if c == 0.0:
                continue
            term = (rows[j] if c == 1.0
                    else -rows[j] if c == -1.0 else c * rows[j])
            acc = term if acc is None else acc + term
        out.append(acc if acc is not None else jnp.zeros_like(rows[0]))
    return out


def _wino_fused_body(x_ref, u_ref, b_ref, o_ref, acc_ref, *, kth: int,
                     ktw: int, mh: int, mw: int, nth: int, ntw: int,
                     rh: int, rw: int, th: int, tw: int, sh: int,
                     sw: int, res_h: int, res_w: int, act: str,
                     bt_h: np.ndarray, bt_w: np.ndarray,
                     at_h: np.ndarray, at_w: np.ndarray):
    """One (batch, row-tile, col-tile, cout-tile, cin-tile) grid step.

    The band covers ``nth x ntw`` Winograd tiles = ``nth*mh`` conv rows
    (``>= rh``, the rows the direct kernel would compute) with the same
    ``(K_T - 1)`` halo — so the padded input window is exactly the
    direct kernel's.  Per cin tile the body
    extracts the ``alpha``-wide tile windows (static strided slices),
    applies ``B^T d B`` (two small VPU matmuls), and accumulates the
    ``alpha_h*alpha_w`` batched MXU matmuls ``V[k] @ U[k]`` into the
    transformed-domain scratch.  The epilogue at the last cin tile
    applies ``A^T M A``, trims the over-computed tile rows, and reuses
    the direct kernel's interleave + bias + act + residual-crop tail.
    """
    ci = pl.program_id(4)

    @pl.when(ci == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    xb = x_ref[0]
    ah, aw = mh + kth - 1, mw + ktw - 1
    xb = xb.astype(jnp.float32)
    tcin = xb.shape[-1]
    # Tile windows: element [a1, a2] of tile (t1, t2) is band row
    # a1 + mh*t1, col a2 + mw*t2 — alpha^2 static strided slices.
    d = [[xb[a1:a1 + mh * nth:mh, a2:a2 + mw * ntw:mw]
          for a2 in range(aw)] for a1 in range(ah)]
    # V = B^T d B, per dim, as unrolled scalar multiply-adds.
    vh = [_mat_rows(bt_h, [d[a1][a2] for a1 in range(ah)])
          for a2 in range(aw)]            # [a2][x1] -> (nth, ntw, tcin)
    v = [_mat_rows(bt_w, [vh[a2][x1] for a2 in range(aw)])
         for x1 in range(ah)]             # [x1][x2]
    # alpha^2 elementwise-in-the-transform-domain MXU matmuls, batched:
    # (ah*aw, nth*ntw, tcin) x (ah*aw, tcin, TCout*ss).
    v3 = jnp.stack([v[x1][x2] for x1 in range(ah)
                    for x2 in range(aw)]).reshape(
        ah * aw, nth * ntw, tcin)
    u3 = u_ref[...].astype(jnp.float32).reshape(ah * aw, tcin, -1)
    mprod = jax.lax.dot_general(v3, u3, (((2,), (1,)), ((0,), (0,))),
                                preferred_element_type=jnp.float32)
    acc_ref[...] += mprod.reshape(acc_ref.shape)

    @pl.when(ci == pl.num_programs(4) - 1)
    def _epilogue():
        cphase = acc_ref.shape[-1]                # TCout * sh*sw
        tc = cphase // (sh * sw)
        mm = acc_ref[...].reshape(ah, aw, nth, ntw, cphase)
        ml = [[mm[x1, x2] for x2 in range(aw)] for x1 in range(ah)]
        # Y = A^T M A, per dim, unrolled like the input transform.
        yh = [_mat_rows(at_h, [ml[x1][x2] for x1 in range(ah)])
              for x2 in range(aw)]        # [x2][o1] -> (nth, ntw, c)
        yo = [_mat_rows(at_w, [yh[x2][o1] for x2 in range(aw)])
              for o1 in range(mh)]        # [o1][o2]
        y = jnp.stack([jnp.stack(row) for row in yo])  # (mh, mw, ...)
        y = y.transpose(2, 0, 3, 1, 4)            # (nth, mh, ntw, mw, c)
        y = y.reshape(nth * mh, ntw * mw, cphase)[:rh, :rw]
        y = y.reshape(rh, rw, tc, sh, sw)         # c -> (oc, py, px)
        y = y.transpose(0, 3, 1, 4, 2)            # (rh, py, rw, px, oc)
        y = y.reshape(rh * sh, rw * sw, tc)
        y = y + b_ref[0].astype(jnp.float32)      # per-oc bias
        y = _apply_act(y, act)
        y = y[res_h:res_h + th * sh, res_w:res_w + tw * sw]
        o_ref[0] = y.astype(o_ref.dtype)


def sd_wino_pallas(x: jax.Array, u: jax.Array, kt: Tuple[int, int], s,
                   *, bias: jax.Array | None = None,
                   act: str = "linear", th: int = 8, tw: int = 0,
                   tcout: int | None = None, tcin: int | None = None,
                   pad: Tuple[PadPair, PadPair] = ((0, 0), (0, 0)),
                   crop: Tuple[int, int] = (0, 0),
                   out_space: Optional[Tuple[int, int]] = None,
                   out_dtype=None, interpret: bool) -> jax.Array:
    """Fused Winograd SD: transformed-domain split conv + interleaved
    write — the Winograd sibling of
    :func:`repro.kernels.sd_conv.sd_fused_pallas` (same contract; see
    its docstring for the pad/crop/out_space semantics).

    x: (B, H, W, Cin) unpadded; u: pre-transformed oc-major filters
    ``(alpha_h, alpha_w, Cin, Cout*sh*sw)`` from
    :func:`transform_filters`; ``kt = (KTh, KTw)`` names the original
    tap geometry (it is no longer readable off ``u``).  No int8 path.
    """
    sh, sw = (s, s) if isinstance(s, int) else (int(s[0]), int(s[1]))
    b, h, wd, cin = x.shape
    kth, ktw = kt
    mh, mw = output_tile(kth), output_tile(ktw)
    ah, aw = mh + kth - 1, mw + ktw - 1
    assert u.shape[0] == ah and u.shape[1] == aw, (u.shape, kt)
    cout = u.shape[-1] // (sh * sw)
    if out_dtype is None:
        out_dtype = x.dtype
    (plo_h, phi_h), (plo_w, phi_w) = pad
    full_oh = h + plo_h + phi_h - kth + 1     # conv rows incl. pad
    full_ow = wd + plo_w + phi_w - ktw + 1
    oh, ow = (out_space if out_space is not None
              else (full_oh * sh, full_ow * sw))
    c_h, c_w = crop
    q_h, res_h = c_h // sh, c_h % sh
    q_w, res_w = c_w // sw, c_w % sw
    tcout = tcout or cout
    tcin = tcin or cin
    assert cout % tcout == 0 and cin % tcin == 0
    if bias is None:
        bias = jnp.zeros((cout,), jnp.float32)
    bias2d = bias.astype(jnp.float32).reshape(1, cout)

    th = min(th, _cdiv(oh, sh))
    tw = tw or _cdiv(ow, sw)
    tw = min(tw, _cdiv(ow, sw))
    nh, nw = _cdiv(oh, th * sh), _cdiv(ow, tw * sw)
    rh = th + (1 if res_h else 0)             # conv rows needed per step
    rw = tw + (1 if res_w else 0)
    nth, ntw = _cdiv(rh, mh), _cdiv(rw, mw)   # Winograd tiles per step
    band_h = nth * mh + kth - 1               # computed rows + halo
    band_w = ntw * mw + ktw - 1
    # The same padded input window as the direct kernel.
    xw = _window(x, (plo_h - q_h, plo_w - q_w),
                 ((nh - 1) * th + band_h, (nw - 1) * tw + band_w))

    at_h, _, bt_h = winograd_matrices(mh, kth)
    at_w, _, bt_w = winograd_matrices(mw, ktw)
    grid = (b, nh, nw, cout // tcout, cin // tcin)
    body = functools.partial(
        _wino_fused_body, kth=kth, ktw=ktw, mh=mh, mw=mw, nth=nth,
        ntw=ntw, rh=rh, rw=rw, th=th, tw=tw, sh=sh, sw=sw, res_h=res_h,
        res_w=res_w, act=act, bt_h=bt_h, bt_w=bt_w, at_h=at_h, at_w=at_w)
    ss = sh * sw
    return pl.pallas_call(
        body,
        grid=grid,
        in_specs=[
            _band_spec(band_h, band_w, tcin, th, tw, nw, cin // tcin),
            pl.BlockSpec((ah, aw, tcin, tcout * ss),
                         lambda bi, i, j, co, ci: (0, 0, ci, co)),
            pl.BlockSpec((1, tcout), lambda bi, i, j, co, ci: (0, co)),
        ],
        out_specs=pl.BlockSpec((1, th * sh, tw * sw, tcout),
                               lambda bi, i, j, co, ci: (bi, i, j, co)),
        out_shape=jax.ShapeDtypeStruct((b, oh, ow, cout), out_dtype),
        scratch_shapes=[pltpu.VMEM(
            (ah * aw * nth * ntw, tcout * ss), jnp.float32)],
        compiler_params=_compiler_params(4, 1),
        interpret=interpret,
    )(xw, u, bias2d)
