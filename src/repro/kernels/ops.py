"""jit'd public wrappers around the Pallas kernels.

``interpret`` defaults to True on CPU backends and False on TPU, so the
same call sites work in both environments.  Interpret mode is refused
on a TPU: there the compiled kernels run, never the interpreter.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from typing import NamedTuple

from repro import obs as _obs

from . import ref as _ref
from .flash_decode import flash_decode as _flash_decode
from .mixed_res import (H_CHK, H_DBAR, H_DWQ, H_INF, H_LAM, H_STEP,
                        mixed_res_dequant_reduce, mixed_res_emit,
                        mixed_res_reduce)
from .quant_pack import sign_dequant_reduce as _sdr
from .quant_pack import signpack as _signpack
from .wire import WirePath, check_packed_dim


def _interpret(interpret: bool | None) -> bool:
    """Resolve a per-call ``interpret`` flag: None picks the backend's
    default (interpret everywhere but a TPU); True is refused on a
    TPU, where the compiled kernels always run."""
    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        return not on_tpu
    if interpret and on_tpu:
        raise ValueError("Pallas interpret mode is the CPU correctness "
                         "harness; on a TPU the compiled kernels run")
    return interpret


def _default_use_kernel(use_kernel: bool | None) -> bool:
    """The fused wire path has two lowerings of the same streaming
    pipeline: the Pallas kernels (the TPU target; run under
    interpret=True on CPU — the parity suite pins them bit-identical
    to the jnp lowering) and the jnp composition of the ref.py oracles
    under the caller's jit (what CPU call sites actually execute —
    interpret mode is a correctness harness, not a fast path)."""
    if use_kernel is None:
        return jax.default_backend() == "tpu"
    return use_kernel


def _resolve_lowering(path: WirePath | None, interpret: bool | None,
                      use_kernel: bool | None) -> tuple:
    """One shared lowering decision for every wire op: a WirePath spec
    wins; the legacy per-call ``interpret``/``use_kernel`` booleans are
    honored when no spec is given (they remain the kernel test suite's
    harness knobs)."""
    if path is not None:
        kern = path.use_kernel() if use_kernel is None else use_kernel
        if interpret is None:
            interpret = path.interpret()
    else:
        kern = _default_use_kernel(use_kernel)
    return kern and _interpret(interpret), kern


@functools.partial(jax.jit, static_argnames=("interpret",))
def signpack_op(x: jnp.ndarray, interpret: bool | None = None
                ) -> jnp.ndarray:
    """Pack the sign plane of a flat f32 vector.

    x: [d] f32 with d % 128 == 0  ->  [d/32] uint32 (viewed flat)."""
    interp = _interpret(interpret)
    words = _signpack(x.reshape(-1, 128), interpret=interp)
    return words.reshape(-1)


@functools.partial(jax.jit, static_argnames=("interpret",))
def sign_dequant_reduce_op(words: jnp.ndarray, scales: jnp.ndarray,
                           interpret: bool | None = None) -> jnp.ndarray:
    """words: [G, d/32] u32, scales: [G] -> [d] f32 weighted sign sum."""
    interp = _interpret(interpret)
    G = words.shape[0]
    out = _sdr(words.reshape(G, -1, 4), scales, interpret=interp)
    return out.reshape(-1)


def sign_pad_len(d: int) -> int:
    """Padded length for viewing a flat d-vector as signpack's [W, 128]
    rows with a valid block partition: W = ceil(d/128), padded up to a
    multiple of 256 rows once W exceeds one block."""
    rows = -(-d // 128)
    if rows > 256 and rows % 256:
        rows = -(-rows // 256) * 256
    return rows * 128


def packed_sign_weighted_sum(flat: jnp.ndarray, scales: jnp.ndarray,
                             interpret: bool | None = None) -> jnp.ndarray:
    """flat: [G, d] f32, scales: [G] f32 -> [d] f32 equal to
    ``sum_g scales_g * sign(flat_g)`` with sign(x) = +1 iff x > 0.

    Routes through the packed wire format: one signpack launch bit-packs
    all G sign planes ([G*W, 128] f32 -> uint32 words, the arrays a
    multi-peer aggregation actually moves), then sign_dequant_reduce
    fuses per-peer unpacking with the scale-weighted reduction.  Not
    jitted here — call sites trace it into their own jitted steps.
    """
    interp = _interpret(interpret)
    G, d = flat.shape
    d_pad = sign_pad_len(d)
    if d_pad != d:
        flat = jnp.pad(flat, ((0, 0), (0, d_pad - d)))
    rows = d_pad // 128
    # the G planes are stacked into one [G*rows, 128] launch, so the
    # block size must divide the per-plane row count (rows <= 256 after
    # sign_pad_len only when it IS the whole plane) — G*rows alone need
    # not be a multiple of the default 256-row block
    bm = rows if rows <= 256 else 256
    words = _signpack(flat.reshape(-1, 128), interpret=interp,
                      block_rows=bm)
    words = words.reshape(G, rows, 4)
    out = _sdr(words, scales.astype(jnp.float32), interpret=interp,
               block_rows=bm)
    return out.reshape(-1)[:d]


# ------------------------------------------------- fused mixed-res wire
class MixedResWire(NamedTuple):
    """Packed wire buffers for U stacked deltas (what a multi-peer
    aggregation actually transmits): sign plane + high-res mask plane
    ([U, W, 4] u32, signpack layout), b-bit magnitude codes
    ([U, W, 4*bw] u32, packing.pack_codes layout) and the per-user
    scalar header row ([U, 8] f32 — inf, dw_q, step, dbar, lambda)."""
    signs: jnp.ndarray
    hi: jnp.ndarray
    codes: jnp.ndarray
    head: jnp.ndarray


def _tap_wire(name: str, users: int, dense_bytes: int,
              wire: "MixedResWire") -> None:
    """Stream the wire path's traffic to the active obs session: bytes
    in/out per fused encode/decode launch (static shape products, so
    the tap carries no device values beyond the callback token; the
    report CLI turns totals into attained vs roofline bandwidth).
    Trace-time gated — stages nothing without a session."""
    if not _obs.jit_stream_enabled():
        return
    packed = sum(int(a.size) * a.dtype.itemsize
                 for a in (wire.signs, wire.hi, wire.codes, wire.head))
    if name == "wire.encode":
        _obs.jit_tap(name, {"bytes_in": dense_bytes,
                            "bytes_out": packed, "users": users})
    else:
        _obs.jit_tap(name, {"bytes_in": packed,
                            "bytes_out": dense_bytes, "users": users})


def wire_view(flat: jnp.ndarray):
    """[U, d] f32 -> zero-padded [U, W, 128] rows (W per sign_pad_len,
    so the kernels' block partition is always valid)."""
    U, d = flat.shape
    d_pad = sign_pad_len(d)
    if d_pad != d:
        flat = jnp.pad(flat, ((0, 0), (0, d_pad - d)))
    return flat.reshape(U, d_pad // 128, 128)


def wire_checksum(wire: "MixedResWire") -> jnp.ndarray:
    """[U] uint32 xor-fold over every packed uint32 word of each user's
    sign/hi/code planes — the integrity word carried in header lane
    ``H_CHK`` when ``WirePath(checksum=True)``.

    Both lowerings share the jnp fold (ref.xor_fold_words_ref): the
    planes are bit-exact across Pallas/interpret/jnp, and xor is
    order-free, so the checksum is lowering-invariant by construction.
    Each plane folds separately (then the three [U] words xor) — a
    concatenated [U, n] staging copy would double the checksum's
    memory traffic against its <5% wire-path overhead budget."""
    U = wire.signs.shape[0]
    chk = _ref.xor_fold_words_ref(wire.signs.reshape(U, -1))
    chk ^= _ref.xor_fold_words_ref(wire.hi.reshape(U, -1))
    return chk ^ _ref.xor_fold_words_ref(wire.codes.reshape(U, -1))


def stamp_checksum(wire: "MixedResWire") -> "MixedResWire":
    """Store the xor-fold checksum in header lane H_CHK (bitcast to the
    f32 header row — the bit pattern is never read arithmetically)."""
    chk = jax.lax.bitcast_convert_type(wire_checksum(wire), jnp.float32)
    return wire._replace(head=wire.head.at[:, H_CHK].set(chk))


def verify_wire(wire: "MixedResWire") -> jnp.ndarray:
    """[U] bool — recompute the plane checksum and compare against the
    header word stamped at encode.  Only meaningful for wires produced
    under ``WirePath(checksum=True)``; jit-safe (no host sync), so
    callers fold the verdict into quarantine masks inside the step."""
    stored = jax.lax.bitcast_convert_type(
        wire.head[:, H_CHK].astype(jnp.float32), jnp.uint32)
    return wire_checksum(wire) == stored


def mixed_res_encode(flat: jnp.ndarray, lambda_: float, b: int, *,
                     interpret: bool | None = None,
                     use_kernel: bool | None = None,
                     path: WirePath | None = None) -> MixedResWire:
    """Threshold-rule (paper eq. 6) encode of U stacked deltas straight
    to the packed wire format — two streaming passes, no dense recon.

    flat: [U, d] f32.  Not jitted here; call sites trace it into their
    own jitted steps."""
    flat = flat.astype(jnp.float32)
    U, d = flat.shape
    # both lowerings accumulate the high-res count in f32 — refuse
    # identically on every backend via the shared WirePath-level guard
    check_packed_dim(d, where="mixed_res_encode")
    x3 = wire_view(flat)
    interp, kern = _resolve_lowering(path, interpret, use_kernel)
    if kern:
        stats = mixed_res_reduce(x3, lambda_, d, interpret=interp)
    else:
        stats = _ref.mixed_res_reduce_ref(x3, lambda_, d)
    # scalar epilogue — identical op sequence to the jnp reference
    inf = stats[:, H_INF]
    dw_q_raw = stats[:, H_DWQ]
    dw_q = jnp.where(jnp.isfinite(dw_q_raw), dw_q_raw, 0.0)
    step = (inf - dw_q) / (2 ** b - 1)
    head = stats.at[:, H_DWQ].set(dw_q).at[:, H_STEP].set(step) \
                .at[:, H_LAM].set(lambda_)
    if kern:
        signs, hi, codes = mixed_res_emit(x3, head, b, d,
                                          interpret=interp)
    else:
        signs, hi, codes = _ref.mixed_res_emit_ref(x3, head, b, d)
    wire = MixedResWire(signs=signs, hi=hi, codes=codes, head=head)
    if path is not None and path.checksum:
        wire = stamp_checksum(wire)
    _tap_wire("wire.encode", int(U), flat.size * 4, wire)
    return wire


def mixed_res_encode_anchored(flat: jnp.ndarray, inf: jnp.ndarray,
                              dw_q: jnp.ndarray, b: int, *,
                              interpret: bool | None = None,
                              use_kernel: bool | None = None,
                              path: WirePath | None = None
                              ) -> MixedResWire:
    """Static-budget (``|x| >= dw_q``) encode used by repro.dist: the
    grid anchor comes from an upstream top-k, so only the emit pass
    runs.  flat: [U, d]; inf/dw_q: [U] f32."""
    flat = flat.astype(jnp.float32)
    U, d = flat.shape
    x3 = wire_view(flat)
    step = (inf - dw_q) / (2 ** b - 1)
    head = jnp.zeros((U, 8), jnp.float32)
    head = head.at[:, H_INF].set(inf).at[:, H_DWQ].set(dw_q) \
               .at[:, H_STEP].set(step)
    interp, kern = _resolve_lowering(path, interpret, use_kernel)
    if kern:
        signs, hi, codes = mixed_res_emit(x3, head, b, d, anchored=True,
                                          interpret=interp)
    else:
        signs, hi, codes = _ref.mixed_res_emit_ref(x3, head, b, d,
                                                   anchored=True)
    wire = MixedResWire(signs=signs, hi=hi, codes=codes, head=head)
    if path is not None and path.checksum:
        wire = stamp_checksum(wire)
    _tap_wire("wire.encode", int(U), flat.size * 4, wire)
    return wire


def mixed_res_wire_reduce(wire: MixedResWire, weights: jnp.ndarray,
                          b: int, d: int, *,
                          acc: jnp.ndarray | None = None,
                          interpret: bool | None = None,
                          use_kernel: bool | None = None,
                          path: WirePath | None = None) -> jnp.ndarray:
    """Fused decode + weighted reduce: sum_g weights_g * deq(wire_g)
    -> [d] f32, entirely from the packed buffers.

    ``acc`` ([d] f32, optional) chains the reduce across cohort chunks:
    the result is ``acc + sum_g w_g * deq(wire_g)`` folded so the
    chunked accumulation over a partitioned user axis reproduces the
    one-shot reduce's summation order (jnp lowering exactly; Pallas
    kernel to chunking-order ulps — DESIGN.md §12)."""
    interp, kern = _resolve_lowering(path, interpret, use_kernel)
    w = weights.astype(jnp.float32)
    acc3 = None
    if acc is not None:
        # view the carried [d] plane in the kernels' [W, 128] layout
        acc3 = wire_view(acc.astype(jnp.float32)[None])[0]
    if kern:
        out = mixed_res_dequant_reduce(wire.signs, wire.hi, wire.codes,
                                       wire.head, w, b, acc=acc3,
                                       interpret=interp)
    else:
        out = _ref.mixed_res_dequant_reduce_ref(
            wire.signs, wire.hi, wire.codes, wire.head, w, b, acc=acc3)
    _tap_wire("wire.decode", int(wire.head.shape[0]), d * 4, wire)
    return out.reshape(-1)[:d]


def mixed_res_wire_aggregate(flat: jnp.ndarray, weights: jnp.ndarray,
                             lambda_: float, b: int, *,
                             interpret: bool | None = None,
                             use_kernel: bool | None = None,
                             path: WirePath | None = None):
    """The whole quantize-to-wire aggregation of the paper's scheme:
    encode U stacked deltas (two streaming passes) and reduce
    ``sum_g w_g * deq(wire_g)`` from the packed buffers.

    Returns ``(agg [d], bits [U], aux)`` where ``bits`` replays the
    reference accounting ``d (b s + 1 - s) + 32`` exactly (``dbar`` is
    an exact integer count) and ``aux`` mirrors
    ``mixed_resolution_quantize``'s aux dict.  The dense per-user
    reconstructions are never materialized."""
    U, d = flat.shape
    wire = mixed_res_encode(flat, lambda_, b, interpret=interpret,
                            use_kernel=use_kernel, path=path)
    agg = mixed_res_wire_reduce(wire, weights, b, d,
                                interpret=interpret,
                                use_kernel=use_kernel, path=path)
    inf = wire.head[:, H_INF]
    dw_q = wire.head[:, H_DWQ]
    dbar = wire.head[:, H_DBAR]
    s = dbar / d
    bits = d * (b * s + 1.0 - s) + 32.0
    bits = jnp.where(inf > 0, bits, float(d) + 32.0)
    aux = {"s": s, "dbar": dbar.astype(jnp.int32), "r": inf - dw_q,
           "dw_q": dw_q, "inf": inf}
    return agg, bits, aux


def segmented_wire_aggregate(flat: jnp.ndarray, weights: jnp.ndarray,
                             segments, *,
                             interpret: bool | None = None,
                             use_kernel: bool | None = None,
                             path: WirePath | None = None):
    """Per-layer-budget wire aggregation (DESIGN.md §13): one
    :func:`mixed_res_wire_aggregate` per contiguous budget segment,
    each with its own ``(lambda_, b)``, concatenated back into the full
    [d] aggregate.

    ``segments``: an ordered iterable of objects with ``start``,
    ``size``, ``lambda_`` and ``b`` attributes tiling [0, d)
    contiguously (``repro.core.quantize.Segment``; duck-typed so this
    module stays import-independent of core.quantize — the contiguity
    check is structural).  Returns ``(agg [d], bits [U], aux)`` where
    ``bits`` is the EXACT sum of the per-segment payloads (one 32-bit
    header per segment) and ``aux["segment_bits"]`` [U, n_seg] is the
    per-segment breakdown that sum is taken over.
    """
    U, d = flat.shape
    segments = tuple(segments)
    offset = 0
    for seg in segments:
        if seg.start != offset or seg.size <= 0:
            raise ValueError(
                f"segments must tile the flat vector contiguously: "
                f"segment {seg} at expected offset {offset}")
        offset += seg.size
    if offset != d:
        raise ValueError(
            f"segments cover {offset} entries but the flat vector has {d}")
    aggs, seg_bits, dbar = [], [], None
    for seg in segments:
        agg_s, bits_s, aux_s = mixed_res_wire_aggregate(
            flat[:, seg.start:seg.start + seg.size], weights,
            seg.lambda_, seg.b, interpret=interpret,
            use_kernel=use_kernel, path=path)
        aggs.append(agg_s)
        seg_bits.append(bits_s)
        db = aux_s["dbar"]
        dbar = db if dbar is None else dbar + db
    agg = jnp.concatenate(aggs)
    segment_bits = jnp.stack(seg_bits, axis=1)           # [U, n_seg]
    bits = jnp.sum(segment_bits, axis=1)
    aux = {"s": dbar.astype(jnp.float32) / float(d),
           "dbar": dbar.astype(jnp.int32),
           "segment_bits": segment_bits}
    return agg, bits, aux


@functools.partial(jax.jit, static_argnames=("interpret", "kv_block"))
def flash_decode_op(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    length: jnp.ndarray, kv_block: int = 512,
                    interpret: bool | None = None) -> jnp.ndarray:
    """Single-token GQA decode attention.

    q: [B, H, D]; k/v: [B, S, Hkv, D(v)]; length: scalar int32.
    Returns [B, H, Dv]."""
    interp = _interpret(interpret)
    B, H, D = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    qg = q.reshape(B, Hkv, G, D)
    kt = k.transpose(0, 2, 1, 3)     # [B, Hkv, S, D]
    vt = v.transpose(0, 2, 1, 3)
    out = _flash_decode(qg, kt, vt, length, kv_block=kv_block,
                        interpret=interp)
    return out.reshape(B, H, -1)
