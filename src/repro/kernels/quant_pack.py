"""Pallas TPU kernels for the quantized-aggregation wire format.

These are the bandwidth-bound hot spots of the paper's technique at
datacenter scale: packing the 1-bit sign plane of a 10^8-element delta
shard, and the fused multi-peer dequantize+weighted-reduce after the
all-gather.  Both are elementwise streaming transforms -> VMEM-tiled
elementwise kernels with 128-lane last dims.

Layout convention: the flat f32 vector is viewed as [W, 128] (W = d /
128 rows); its packed sign plane is [W, 4] uint32 (4 words x 32 bits =
128 lanes).  The host-side reshape is free (layout-only).

Mosaic (the TPU compiler for Pallas) does not split the lane axis
((bm, 128) -> (bm, 4, 32)) and has no unsigned reductions, so the bit
packing here runs on the MXU instead: :func:`pack_lanes` multiplies the
lane values by a 0/power-of-two selection matrix.  Every operand is
exact in bf16 and every partial sum stays below 2**16, so the matmul
is exact at any MXU precision and the words are bit-identical to the
shift-and-sum jnp oracles in ``ref.py``.  Unpacking
(:func:`unpack_lanes`) broadcasts each word over its lanes, which
Mosaic does lower, and shifts per lane.

TARGET is TPU; on CPU the kernels run under interpret=True (see
ops.py and tests/test_kernels.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_ROWS = 256            # rows of 128 lanes per VMEM tile


def pack_lanes(vals: jnp.ndarray, bw: int) -> jnp.ndarray:
    """[R, 128] f32 of unsigned integers < 2**bw -> [R, 128*bw/32]
    uint32.  Word k holds lanes ``k*per .. k*per+per-1`` (per = 32/bw),
    lane j of the word at bit ``j*bw`` — the ``packing.pack_codes`` and
    ``signpack`` layouts.

    The low and high 16-bit halves of each word are two selection
    matmuls with power-of-two weights below 2**16; 16-bit values are
    split into bytes first so every matmul operand is exact in bf16."""
    per = 32 // bw
    nw = 128 // per
    lane = jax.lax.broadcasted_iota(jnp.int32, (128, nw), 0)
    word = jax.lax.broadcasted_iota(jnp.int32, (128, nw), 1)
    log_per = per.bit_length() - 1              # per is a power of two
    shift = (lane & (per - 1)) * bw
    mine = jnp.right_shift(lane, log_per) == word
    weight = jnp.left_shift(1, shift & 15).astype(jnp.float32)
    lo_w = jnp.where(mine & (shift < 16), weight, 0.0)
    hi_w = jnp.where(mine & (shift >= 16), weight, 0.0)
    parts = [(vals, 1.0)]
    if bw > 8:
        top = jnp.floor(vals * (1.0 / 256.0))
        parts = [(vals - top * 256.0, 1.0), (top, 256.0)]

    def half(w):
        out = None
        for v, scale in parts:
            term = jnp.dot(v, w, preferred_element_type=jnp.float32) * scale
            out = term if out is None else out + term
        return out.astype(jnp.int32)

    words = half(lo_w) | jnp.left_shift(half(hi_w), 16)
    return jax.lax.bitcast_convert_type(words, jnp.uint32)


def unpack_lanes(words: jnp.ndarray, bw: int) -> jnp.ndarray:
    """Inverse of :func:`pack_lanes`: [R, 128*bw/32] uint32 -> [R, 128]
    int32 fields (each < 2**bw)."""
    per = 32 // bw
    R, nw = words.shape
    w = jax.lax.bitcast_convert_type(words, jnp.int32)
    w = jnp.broadcast_to(w[:, :, None], (R, nw, per)).reshape(R, 128)
    shift = (jax.lax.broadcasted_iota(jnp.int32, (R, 128), 1)
             & (per - 1)) * bw
    return jax.lax.shift_right_logical(w, shift) & ((1 << bw) - 1)


def _signpack_kernel(x_ref, out_ref):
    """x_ref: [bm, 128] f32 -> out_ref: [bm, 4] uint32."""
    out_ref[...] = pack_lanes((x_ref[...] > 0).astype(jnp.float32), 1)


def signpack(x: jnp.ndarray, *, interpret: bool = False,
             block_rows: int = BLOCK_ROWS) -> jnp.ndarray:
    """x: [W, 128] f32 -> [W, 4] uint32 packed sign plane."""
    W = x.shape[0]
    bm = min(block_rows, W)
    assert W % bm == 0, (W, bm)
    return pl.pallas_call(
        _signpack_kernel,
        grid=(W // bm,),
        in_specs=[pl.BlockSpec((bm, 128), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((bm, 4), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((W, 4), jnp.uint32),
        interpret=interpret,
    )(x)


def _sign_dequant_reduce_kernel(words_ref, scales_ref, out_ref):
    """Grid (tile, peer).  words_ref: [1, bm, 4] u32 of peer g;
    scales_ref: [1, 1, 1] f32; out_ref: [bm, 128] f32, resident across
    the peer axis, accumulates scale_g * signs_g."""
    g = pl.program_id(1)
    scale = scales_ref[0]                                   # [1, 1]
    term = jnp.where(unpack_lanes(words_ref[0], 1) != 0, scale, -scale)

    @pl.when(g == 0)
    def _():
        out_ref[...] = term

    @pl.when(g > 0)
    def _():
        out_ref[...] = out_ref[...] + term


def sign_dequant_reduce(words: jnp.ndarray, scales: jnp.ndarray, *,
                        interpret: bool = False,
                        block_rows: int = BLOCK_ROWS) -> jnp.ndarray:
    """words: [G, W, 4] u32, scales: [G] f32 -> [W, 128] f32.

    Fuses per-peer sign unpacking with the rho-weighted reduction over
    peers: the G x d intermediate float planes never hit HBM.  Peers
    ride the inner grid axis, so one peer's tile is resident at a time.
    """
    G, W, _ = words.shape
    bm = min(block_rows, W)
    assert W % bm == 0, (W, bm)
    return pl.pallas_call(
        _sign_dequant_reduce_kernel,
        grid=(W // bm, G),
        in_specs=[pl.BlockSpec((1, bm, 4), lambda i, g: (g, i, 0)),
                  pl.BlockSpec((1, 1, 1), lambda i, g: (g, 0, 0))],
        out_specs=pl.BlockSpec((bm, 128), lambda i, g: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((W, 128), jnp.float32),
        interpret=interpret,
    )(words, scales.astype(jnp.float32).reshape(G, 1, 1))
