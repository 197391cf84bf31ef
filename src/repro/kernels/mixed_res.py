"""Fused Pallas mixed-resolution encode/decode — quantize-to-wire in
two streaming passes.

The paper's adaptive mixed-resolution quantization (eqs. 6-8,
``core/quantize/mixed_resolution.py``) is the per-user, per-round hot
path of the reproduction.  The pure-jnp reference makes ~8 full passes
over the d-element delta (abs/max/mask/min-where/round/three wheres),
materializes a dense f32 reconstruction, and leaves wire packing
(``core/quantize/packing.py``) as yet another downstream pass.  These
kernels collapse the whole encode into two streaming passes over VMEM
tiles and fuse the server-side decode with the multi-user weighted
reduction, so the dense reconstruction never exists anywhere:

* **pass A** (:func:`mixed_res_reduce`) — per-tile reductions of
  ``||x||_inf`` (grid phase 0), then the threshold-masked minimum
  ``dw_q`` and the high-resolution count ``dbar`` (grid phase 1, which
  needs the phase-0 max), tree-combined across the grid into one
  8-lane scalar row per user;
* **pass B** (:func:`mixed_res_emit`) — consumes the per-user scalar
  header and emits the packed wire format directly: uint32 sign-plane
  words, uint32 high-resolution mask words (both in the ``signpack``
  ``[W, 4]`` layout) and ``b``-bit magnitude codes packed
  ``32 // bw`` per word in the ``packing.pack_codes`` layout;
* **decode** (:func:`mixed_res_dequant_reduce`) — unpacks the G users'
  wire buffers tile-by-tile and folds ``sum_g w_g * recon_g`` in one
  kernel, users on the inner grid axis; the per-user dense planes live
  only as one VMEM tile each.

Layout convention (same as ``quant_pack.py``): the flat f32 vector is
viewed as ``[W, 128]`` rows; sign/hi planes pack to ``[W, 4]`` uint32;
the code plane packs to ``[W, 4 * bw]`` uint32 where ``bw`` is the
code *storage* width — the smallest of {2, 4, 8, 16} that holds ``b``
bits (the paper's b = 10 stores in 16; the *accounted* payload uses
the true ``b``, see DESIGN.md section 9).  A leading user axis U rides
the grid, never a vmap.

The per-user header row travels as a ``[U, 1, 8]`` f32 block (a full
trailing window, which Mosaic accepts for any U) and is read and
written as a vector: lanes are picked with an iota mask, never by a
scalar store into VMEM.  Bit packing goes through ``quant_pack``'s
MXU-exact :func:`pack_lanes` / :func:`unpack_lanes`.

TARGET is TPU; on CPU the kernels run under interpret=True (see
``ops.py``).  The jnp oracles live in ``ref.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .quant_pack import BLOCK_ROWS, pack_lanes, unpack_lanes

# wire-header lane assignment ([U, 8] f32 scalar rows).  Lane H_CHK
# carries the bitcast uint32 xor-fold checksum of the packed planes
# when WirePath(checksum=True); it is never read arithmetically (the
# bit pattern may alias a NaN) — decode and bit accounting consume
# lanes 0-3 only, so stamping it leaves both bit-for-bit unchanged.
H_INF, H_DWQ, H_STEP, H_DBAR, H_LAM, H_CHK = 0, 1, 2, 3, 4, 5
HEADER_LANES = 8

CODE_STORE_WIDTHS = (2, 4, 8, 16)


def code_width(b: int) -> int:
    """Storage width for b-bit codes: smallest of {2,4,8,16} >= b."""
    for w in CODE_STORE_WIDTHS:
        if w >= b:
            return w
    raise ValueError(f"wire kernels store codes in <= 16 bits, got b={b}")


def code_words_per_row(b: int) -> int:
    """uint32 words per 128-lane row of the packed code plane."""
    return 128 * code_width(b) // 32


def _valid_mask(i, bm: int, d_valid: int):
    """[bm, 128] bool — element's flat index within the real (unpadded)
    vector.  ``d_valid`` is static; callers skip the mask entirely when
    the vector fills its padded view."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (bm, 128), 0)
    lanes = jax.lax.broadcasted_iota(jnp.int32, (bm, 128), 1)
    flat = (i * bm + rows) * 128 + lanes
    return flat < d_valid


# ------------------------------------------------------------ pass A
def _lane(lane: int):
    """[1, HEADER_LANES] bool mask selecting one header lane."""
    return jax.lax.broadcasted_iota(jnp.int32, (1, HEADER_LANES), 1) == lane


def _reduce_kernel(x_ref, out_ref, *, lam: float, bm: int, d_valid: int,
                   masked: bool):
    """Grid (U, 2, T).  Phase 0 accumulates ||x||_inf; phase 1 (which
    reads the phase-0 result from the revisited output row) accumulates
    the threshold-masked min ``dw_q`` and the high-res count ``dbar``.
    out_ref: [1, 1, 8] f32 per user — revisited across (phase, tile),
    so it stays resident in VMEM for the whole per-user reduction."""
    ph = pl.program_id(1)
    i = pl.program_id(2)
    absx = jnp.abs(x_ref[0])

    @pl.when((ph == 0) & (i == 0))
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(ph == 0)
    def _():
        row = out_ref[0]
        out_ref[0] = jnp.where(_lane(H_INF),
                               jnp.maximum(row, jnp.max(absx)), row)

    @pl.when((ph == 1) & (i == 0))
    def _():
        out_ref[0] = jnp.where(_lane(H_DWQ), jnp.inf, out_ref[0])

    @pl.when(ph == 1)
    def _():
        row = out_ref[0]
        inf = row[:, H_INF:H_INF + 1]
        safe_inf = jnp.where(inf > 0, inf, 1.0)
        # the same per-element division the jnp reference uses (NOT
        # absx >= lam * inf, which rounds differently)
        hi = (absx / safe_inf) >= lam
        if masked:
            hi = hi & _valid_mask(i, bm, d_valid)
        dwq = jnp.min(jnp.where(hi, absx, jnp.inf))
        cnt = jnp.sum(hi.astype(jnp.float32))
        row = jnp.where(_lane(H_DWQ), jnp.minimum(row, dwq), row)
        out_ref[0] = jnp.where(_lane(H_DBAR), row + cnt, row)


def mixed_res_reduce(x: jnp.ndarray, lam: float, d_valid: int, *,
                     interpret: bool = False,
                     block_rows: int = BLOCK_ROWS) -> jnp.ndarray:
    """x: [U, W, 128] f32 -> stats [U, 8] f32.

    Lane H_INF holds ``||x||_inf``, H_DWQ the raw threshold-masked min
    (``+inf`` when no element clears the threshold — callers map it to
    0 like the jnp reference), H_DBAR the high-resolution count (exact
    in f32 for d < 2**24).  ``d_valid`` is the unpadded length; pad
    elements never enter the phase-1 mask."""
    U, W, _ = x.shape
    bm = min(block_rows, W)
    assert W % bm == 0, (W, bm)
    if not (0 < d_valid <= W * 128):
        raise ValueError(f"d_valid={d_valid} outside (0, {W * 128}]")
    if d_valid >= 2 ** 24:
        raise ValueError("f32 dbar accumulator is exact only to 2**24")
    kernel = functools.partial(
        _reduce_kernel, lam=float(lam), bm=bm, d_valid=int(d_valid),
        masked=d_valid != W * 128)
    out = pl.pallas_call(
        kernel,
        grid=(U, 2, W // bm),
        in_specs=[pl.BlockSpec((1, bm, 128), lambda u, p, i: (u, i, 0))],
        out_specs=pl.BlockSpec((1, 1, HEADER_LANES),
                               lambda u, p, i: (u, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((U, 1, HEADER_LANES), jnp.float32),
        interpret=interpret,
    )(x)
    return out.reshape(U, HEADER_LANES)


# ------------------------------------------------------------ pass B
def _emit_kernel(x_ref, head_ref, signs_ref, hi_ref, codes_ref, *,
                 bw: int, levels: int, anchored: bool, bm: int,
                 d_valid: int, masked: bool):
    """Grid (U, T): consume the scalar header, emit the wire tile."""
    i = pl.program_id(1)
    x = x_ref[0]
    absx = jnp.abs(x)
    head = head_ref[0]                                  # [1, 8]
    inf = head[:, H_INF:H_INF + 1]
    dw_q = head[:, H_DWQ:H_DWQ + 1]
    step = head[:, H_STEP:H_STEP + 1]
    safe_step = jnp.where(step > 0, step, 1.0)
    if anchored:
        hi = absx >= dw_q                       # static-budget rule
    else:
        safe_inf = jnp.where(inf > 0, inf, 1.0)
        hi = (absx / safe_inf) >= head[:, H_LAM:H_LAM + 1]   # eq. (6)
    if masked:
        hi = hi & _valid_mask(i, bm, d_valid)

    # b-bit magnitude code on the [dw_q, inf] grid; low-res elements
    # would produce negative codes — masked to 0 before packing.
    # The clamp to the grid top is a no-op when the header's inf is the
    # true max (codes never exceed `levels` then), but an anchored
    # header from an approximate top-k (jax.lax.approx_max_k) can
    # underestimate inf — an unclamped code would then spill shifted
    # bits into NEIGHBORING code slots and corrupt other elements;
    # clamped, the overshoot stays element-local (mag caps at inf),
    # like the jnp reference's behaviour.
    code = jnp.round((absx - dw_q) / safe_step)
    code = jnp.minimum(jnp.where(hi, code, 0.0), float(levels))

    signs_ref[0] = pack_lanes((x > 0).astype(jnp.float32), 1)
    hi_ref[0] = pack_lanes(hi.astype(jnp.float32), 1)
    codes_ref[0] = pack_lanes(code, bw)


def mixed_res_emit(x: jnp.ndarray, head: jnp.ndarray, b: int,
                   d_valid: int, *, anchored: bool = False,
                   interpret: bool = False,
                   block_rows: int = BLOCK_ROWS):
    """x: [U, W, 128] f32, head: [U, 8] f32 -> packed wire planes
    (signs [U, W, 4], hi [U, W, 4], codes [U, W, 4*bw]) uint32.

    ``anchored=False`` uses the paper's threshold rule
    ``|x|/||x||_inf >= lambda`` (header lane H_LAM); ``anchored=True``
    uses the static-budget rule ``|x| >= dw_q`` (repro.dist)."""
    U, W, _ = x.shape
    bm = min(block_rows, W)
    assert W % bm == 0, (W, bm)
    bw = code_width(b)
    cpr = code_words_per_row(b)
    kernel = functools.partial(
        _emit_kernel, bw=bw, levels=2 ** b - 1, anchored=anchored,
        bm=bm, d_valid=int(d_valid), masked=d_valid != W * 128)
    return pl.pallas_call(
        kernel,
        grid=(U, W // bm),
        in_specs=[pl.BlockSpec((1, bm, 128), lambda u, i: (u, i, 0)),
                  pl.BlockSpec((1, 1, HEADER_LANES),
                               lambda u, i: (u, 0, 0))],
        out_specs=[pl.BlockSpec((1, bm, 4), lambda u, i: (u, i, 0)),
                   pl.BlockSpec((1, bm, 4), lambda u, i: (u, i, 0)),
                   pl.BlockSpec((1, bm, cpr), lambda u, i: (u, i, 0))],
        out_shape=[jax.ShapeDtypeStruct((U, W, 4), jnp.uint32),
                   jax.ShapeDtypeStruct((U, W, 4), jnp.uint32),
                   jax.ShapeDtypeStruct((U, W, cpr), jnp.uint32)],
        interpret=interpret,
    )(x, head.reshape(U, 1, HEADER_LANES))


# ------------------------------------------------------------- decode
def _dequant_reduce_kernel(signs_ref, hi_ref, codes_ref, ws_ref, *rest,
                           bw: int):
    """Grid (tile, user).  One user's wire tile -> its weighted
    reconstruction, folded into the output tile, which stays resident
    across the user axis.  The per-user dense reconstruction exists
    only as this VMEM tile.  The fold is the jnp oracle's left fold:
    ``((acc + u_0) + u_1) + ...`` with an ``acc`` operand (cohort
    chunking), ``(u_0 + u_1) + ...`` without."""
    if len(rest) == 2:
        acc_ref, out_ref = rest
    else:
        acc_ref, (out_ref,) = None, rest
    g = pl.program_id(1)
    sign = unpack_lanes(signs_ref[0], 1) != 0
    hi = unpack_lanes(hi_ref[0], 1) != 0
    code = unpack_lanes(codes_ref[0], bw).astype(jnp.float32)
    ws = ws_ref[0]                                      # [1, 2]
    wdq, wst = ws[:, 0:1], ws[:, 1:2]
    # eq. (7)/(8): b-bit grid magnitude on the hi support, dw_q/2 off
    # it, with the weight folded into the grid scalars as in the oracle
    mag = jnp.where(hi, wdq + code * wst, wdq * 0.5)
    term = jnp.where(sign, mag, -mag)

    @pl.when(g == 0)
    def _():
        out_ref[...] = term if acc_ref is None else acc_ref[...] + term

    @pl.when(g > 0)
    def _():
        out_ref[...] = out_ref[...] + term


def mixed_res_dequant_reduce(signs: jnp.ndarray, hi: jnp.ndarray,
                             codes: jnp.ndarray, head: jnp.ndarray,
                             weights: jnp.ndarray, b: int, *,
                             acc: jnp.ndarray | None = None,
                             interpret: bool = False,
                             block_rows: int = BLOCK_ROWS) -> jnp.ndarray:
    """signs/hi: [G, W, 4] u32, codes: [G, W, 4*bw] u32, head: [G, 8]
    f32, weights: [G] f32 -> [W, 128] f32 = sum_g w_g * deq(wire_g).

    Fuses per-user wire decoding with the weighted multi-user reduce:
    the G dense f32 reconstruction planes never hit HBM.  Users ride
    the inner grid axis, so VMEM holds one user's tile per plane
    whatever G is.  ``acc`` ([W, 128] f32, optional) adds the reduce
    on top of a carried accumulator tile-by-tile, so cohort chunks of a
    large user axis fold through one resident plane (DESIGN.md §12: the
    same left fold as the jnp oracle, so chunking does not change it)."""
    G, W, _ = signs.shape
    bm = min(block_rows, W)
    assert W % bm == 0, (W, bm)
    bw = code_width(b)
    cpr = code_words_per_row(b)
    assert codes.shape == (G, W, cpr), (codes.shape, cpr)
    kernel = functools.partial(_dequant_reduce_kernel, bw=bw)
    w = weights.astype(jnp.float32)
    # the oracle's per-user grid scalars w*dw_q and w*step
    ws = jnp.stack([w * head[:, H_DWQ], w * head[:, H_STEP]], axis=1)
    in_specs = [pl.BlockSpec((1, bm, 4), lambda i, g: (g, i, 0)),
                pl.BlockSpec((1, bm, 4), lambda i, g: (g, i, 0)),
                pl.BlockSpec((1, bm, cpr), lambda i, g: (g, i, 0)),
                pl.BlockSpec((1, 1, 2), lambda i, g: (g, 0, 0))]
    args = [signs, hi, codes, ws.reshape(G, 1, 2)]
    if acc is not None:
        assert acc.shape == (W, 128), acc.shape
        in_specs.append(pl.BlockSpec((bm, 128), lambda i, g: (i, 0)))
        args.append(acc.astype(jnp.float32))
    return pl.pallas_call(
        kernel,
        grid=(W // bm, G),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, 128), lambda i, g: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((W, 128), jnp.float32),
        interpret=interpret,
    )(*args)
