"""Prior deconv-to-conv conversions the paper compares against (Table 4).

Both are *incorrect* in general — that is the paper's point — and both are
reproduced here so an SSIM comparison against the exact deconvolution
can quantify the damage:

* ``shi_deconv``   — Shi et al. [30] ("Is the deconvolution layer the same
  as a convolutional layer?"): sub-pixel conversion with a *fixed*
  zero-padding on the right/bottom of the input and a fixed filter
  expansion orientation.  Only the first partition's geometry is right;
  when ``K % s != 0`` every other phase reads shifted windows.
* ``chang_deconv`` — Chang & Kang [31] (FPGA super-resolution): an
  approximate filter-deformation that *truncates* the kernel to
  ``s * floor(K/s)`` so it splits evenly, dropping the boundary taps.
  Tolerable for super-resolution, wrong for general GANs.

Both degenerate to the correct result when ``s | K`` — which is exactly why
the paper evaluates them on DCGAN (K=5, s=2) and FST (K=3, s=2).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .deconv import (_pads, _pair, deconv_output_shape, depth_to_space,
                     sd_geometry)


def _split_with_expansion(w, stride, expand_side: str):
    """Split filters with the zero expansion on a chosen side."""
    sh, sw = _pair(stride)
    kh, kw, cin, cout = w.shape
    (kth, ktw), (pkh, pkw), _ = sd_geometry((kh, kw), (sh, sw))
    if expand_side == "top_left":           # correct (paper SD)
        we = jnp.pad(w, ((pkh, 0), (pkw, 0), (0, 0), (0, 0)))
    else:                                    # Shi: bottom/right — wrong
        we = jnp.pad(w, ((0, pkh), (0, pkw), (0, 0), (0, 0)))
    we = we.reshape(kth, sh, ktw, sw, cin, cout)
    we = we[::-1, :, ::-1, :, :, :]
    we = we.transpose(0, 2, 4, 1, 3, 5)
    return we.reshape(kth, ktw, cin, sh * sw * cout)


def shi_deconv(x: jax.Array, w: jax.Array, stride, padding=0) -> jax.Array:
    """[30]'s conversion: fixed right/bottom input padding + fixed crop.

    The blog's recipe pads the *input* with ``K_T - 1`` zeros on the right
    and bottom only and takes the pixel-shuffled conv output verbatim (no
    partition-dependent crop).  That geometry is right for the first
    partition only: the true deconv output is the pixel-shuffle cropped by
    ``P_K + p`` on the top/left, so every other partition's pixels land
    shifted — a structured, image-wide error (paper: SSIM 0.568 on DCGAN,
    0.939 on FST where the shift is visually tolerable).
    """
    (kth, ktw), (pkh, pkw), (pih, piw) = sd_geometry(w.shape[:2], stride)
    (pt, pb), (pl, pr) = _pads(padding)
    oh, ow = deconv_output_shape(x.shape[1:3], w.shape[:2], stride, padding)
    ws = _split_with_expansion(w, stride, "bottom_right")
    # fixed padding: right/bottom only (the paper's complaint)
    xp = jnp.pad(x, ((0, 0), (0, 2 * pih), (0, 2 * piw), (0, 0)))
    y = lax.conv_general_dilated(
        xp, ws, window_strides=(1, 1), padding="VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    ps = depth_to_space(y, stride)
    # fixed crop from the origin — ignores both P_K and the user padding
    return lax.slice(ps, (0, 0, 0, 0),
                     (ps.shape[0], oh, ow, ps.shape[3]))


def chang_deconv(x: jax.Array, w: jax.Array, stride, padding=0) -> jax.Array:
    """[31]'s approximate conversion: truncate the kernel so ``s | K``.

    Drops the first ``K % s`` rows/cols of the filter (the taps the exact
    method would cover via zero expansion), then applies the (now exact)
    split. Returns an output with correct shape but approximated values.
    """
    sh, sw = _pair(stride)
    kh, kw = w.shape[:2]
    dh, dw = kh % sh, kw % sw
    if dh == 0 and dw == 0:
        from .deconv import sd_deconv
        return sd_deconv(x, w, stride, padding)
    wt = w[dh:, dw:]  # truncated (K - K%s) kernel: now divisible
    # adjust padding: removing top/left taps shifts the full output up/left
    # by dh; keep the requested output size by cropping less on top/left.
    (pt, pb), (pl, pr) = _pads(padding)
    oh, ow = deconv_output_shape(x.shape[1:3], (kh, kw), stride, padding)
    from .deconv import sd_deconv as _sd
    full = _sd(x, wt, stride, 0)
    # align: full (truncated) output corresponds to original full output
    # rows [dh:]; crop to the requested window, clamped to bounds.
    st = max(pt - dh, 0)
    sl = max(pl - dw, 0)
    st = min(st, max(full.shape[1] - oh, 0))
    sl = min(sl, max(full.shape[2] - ow, 0))
    return lax.slice(full, (0, st, sl, 0),
                     (full.shape[0], st + oh, sl + ow, full.shape[3]))
