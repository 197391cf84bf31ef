"""Compressed cross-replica delta aggregation — the paper's §II-C
mixed-resolution scheme as a datacenter collective.

Every data-parallel replica plays the role of one FL user: it holds a
local model delta and the aggregation point is the cross-replica mean
(eq. 3 with uniform rho).  ``aggregate_delta`` compresses that exchange
with the static-budget wire format (core/quantize/static_budget.py):

* ``kind="none"``   — fp32 all-reduce mean, bit-exact (the baseline and
  the correctness oracle);
* ``kind="mixed"``  — per replica, the k = ceil(s_budget * d) largest-
  magnitude elements are sent on a ``bits``-wide uniform grid anchored
  at the rank-k magnitude ``dw_q`` (high resolution); every element
  additionally contributes one sign bit, reconstructed as
  ``± dw_q / 2`` outside the top-k support (low resolution).

  ``wire`` (a :class:`repro.kernels.WirePath`, shared with the sim
  engine; the legacy ``wire_path`` strings map onto it through a
  deprecation shim) selects the realization of that exchange:

  * plane ``"packed"`` (default; legacy ``"fused"``) — the streaming
    mixed-res kernel suite
    (``kernels/mixed_res.py``, DESIGN.md §9): after the top-k anchor,
    one emit pass packs sign + hi-mask + b-bit code planes straight to
    uint32 wire buffers and ``mixed_res_dequant_reduce`` fuses the
    multi-peer decode with the weighted reduction — no dense
    reconstruction is ever materialized, and in manual mode the
    collective moves exactly the packed wire buffers — one
    ``all_gather`` (``WirePath.reduce="gather"``) or G-1
    ``collective_permute`` ring hops folding through the chunked
    accumulator (``reduce="ring"``, one peer buffer resident per hop);
  * plane ``"signplane"`` (legacy ``"reference"``) — the original jnp
    path (``mixed_recon`` dense
    roundtrip + packed 1-bit plane through ``signpack`` /
    ``sign_dequant_reduce`` + dense high-res correction), kept as the
    golden reference the fused path is tested against.

  Either way the payload is *accounted* at the packed
  sign+idx+code size (see DESIGN.md §6 for the wire-format layout).

Two calling conventions, one semantics:

* **stacked** (``axis_names`` empty) — leaves carry a leading replica
  axis ``[G, ...]`` laid over the data mesh axis by GSPMD; used by
  ``build_train_step`` (vmap over replicas).
* **manual** (``axis_names`` non-empty) — called inside a fully-manual
  ``shard_map`` region; leaves are the replica-local shards and the
  exchange uses ``all_gather``/``pmean`` over the named axes.  Each
  model shard quantizes independently (per-shard top-k), which is the
  TPU-native layout: no cross-shard sort, and Lemma 1 holds per shard
  with the per-shard realized threshold.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core.quantize.static_budget import wire_bits
from repro.kernels import WirePath, check_packed_dim, from_wire_path
from repro.kernels.ops import (mixed_res_encode_anchored,
                               mixed_res_wire_reduce,
                               packed_sign_weighted_sum)


@dataclasses.dataclass(frozen=True)
class CompressorConfig:
    """Wire-format selection for ``aggregate_delta``."""
    kind: str = "mixed"          # "none" | "mixed"
    s_budget: float = 0.01       # high-resolution fraction (k = ceil(s*d))
    bits: int = 8                # grid width b; must divide 32
    exact_topk: bool = False     # False may use approx_max_k on TPU
    # DEPRECATED spelling of the wire-path plane: "fused" (packed
    # mixed-res kernels) | "reference" (jnp golden signplane path).
    # New call sites set ``wire=WirePath(...)``; None defers to it.
    wire_path: Optional[str] = None
    # The unified wire-path spec (repro.kernels.WirePath) shared with
    # the sim engine.  plane="packed" is the fused kernel exchange,
    # plane="signplane" the golden reference; reduce="ring" replaces
    # manual mode's all_gather with G-1 collective_permute hops (one
    # packed peer buffer resident per hop, folded through the chunked
    # accumulate — DESIGN.md §12).  None + wire_path=None resolves to
    # the packed default.
    wire: Optional[WirePath] = None

    def resolved_wire(self) -> WirePath:
        """The WirePath this config runs: ``wire`` when set, else the
        legacy ``wire_path`` string through its deprecation shim, else
        the packed (fused) default."""
        if self.wire is not None:
            if self.wire_path is not None:
                raise ValueError(
                    "set CompressorConfig.wire OR the legacy wire_path "
                    f"string, not both (wire={self.wire!r}, "
                    f"wire_path={self.wire_path!r})")
            return self.wire
        if self.wire_path is not None:
            return from_wire_path(self.wire_path)
        return WirePath(plane="packed")

    def validate(self) -> None:
        if self.kind not in ("none", "mixed"):
            raise ValueError(f"unknown compressor kind {self.kind!r}")
        wp = self.resolved_wire()   # raises on unknown legacy strings
        if self.kind == "mixed":
            if wp.plane == "dense":
                raise ValueError(
                    "kind='mixed' moves a compressed plane; use "
                    "WirePath(plane='packed') (fused kernels) or "
                    "'signplane' (reference path)")
            if not (0.0 < self.s_budget <= 1.0):
                raise ValueError(f"s_budget must be in (0, 1], got "
                                 f"{self.s_budget}")
            if self.bits < 2 or 32 % self.bits != 0:
                raise ValueError(f"bits must divide 32 and be >= 2, got "
                                 f"{self.bits}")
            if wp.plane == "packed" and self.bits > 16:
                raise ValueError(
                    "the fused wire kernels store codes in <= 16 bits; "
                    f"got bits={self.bits} (use the signplane "
                    "reference plane)")
        budget = getattr(wp, "effective_budget", None)
        if budget is not None:
            if self.kind != "mixed":
                raise ValueError(
                    "per-layer budgets re-parameterize the mixed "
                    f"compressor per segment; kind={self.kind!r} has "
                    "no (s_budget, bits) to segment")
            if wp.reduce == "ring":
                raise ValueError(
                    "per-layer budgets are not supported on the ring "
                    "reduce yet (one accumulator chain per segment); "
                    "use WirePath(reduce='gather')")
            for rule in budget.rules:
                b = self.bits if rule.b is None else rule.b
                if b < 2 or 32 % b != 0:
                    raise ValueError(
                        f"budget group {rule.group!r}: bits must divide "
                        f"32 and be >= 2, got {b}")
                if wp.plane == "packed" and b > 16:
                    raise ValueError(
                        f"budget group {rule.group!r}: the fused wire "
                        f"kernels store codes in <= 16 bits, got {b}")


def budget_k(d: int, s_budget: float) -> int:
    """Static high-resolution budget for a d-element shard."""
    return max(1, min(d, math.ceil(s_budget * d)))


def payload_bits(d: int, comp: CompressorConfig,
                 segments: Optional[Tuple] = None) -> int:
    """Exact per-replica wire payload for one d-element shard.

    With budget ``segments`` (see :func:`aggregate_delta`) the payload
    is the exact sum of the per-segment wire payloads — the bits-sum
    identity of DESIGN.md §13, on the dist side."""
    if comp.kind == "none":
        return 32 * d
    if segments:
        return sum(
            wire_bits(seg.size, budget_k(seg.size, seg.s_budget),
                      seg.b)
            for seg in segments)
    return wire_bits(d, budget_k(d, comp.s_budget), comp.bits)


def _segment_comp(comp: CompressorConfig, wp: WirePath, seg
                  ) -> CompressorConfig:
    """The sub-config one budget segment runs: the segment's
    (s_budget, bits) over a budget-stripped copy of the wire path, so
    the per-segment call reuses the global single-segment machinery."""
    return dataclasses.replace(
        comp, s_budget=seg.s_budget, bits=seg.b,
        wire=dataclasses.replace(wp, budget=None), wire_path=None)


def _rank_k_values(absx: jnp.ndarray, k: int, exact: bool
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(inf-norm, rank-k magnitude) along the last axis."""
    if not exact and jax.default_backend() == "tpu":
        vals, _ = jax.lax.approx_max_k(absx, k)
    else:
        vals, _ = jax.lax.top_k(absx, k)
    return vals[..., 0], vals[..., -1]


def mixed_recon(flat: jnp.ndarray, comp: CompressorConfig
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Element-wise mixed-resolution roundtrip of ``flat`` ([..., d]).

    Returns (recon, dw_q) where dw_q is the per-row grid anchor (the
    rank-k magnitude).  Equivalent to static_budget_encode+decode but
    threshold-based, so it is batchable and never materializes the
    index plane in the compute graph (ties at rank k land in the
    high-resolution branch for every tied element).
    """
    x = flat.astype(jnp.float32)
    d = x.shape[-1]
    k = budget_k(d, comp.s_budget)
    absx = jnp.abs(x)
    inf, dw_q = _rank_k_values(absx, k, comp.exact_topk)
    levels = 2 ** comp.bits - 1
    step = (inf - dw_q) / levels
    safe_step = jnp.where(step > 0, step, 1.0)
    code = jnp.round((absx - dw_q[..., None]) / safe_step[..., None])
    mags = dw_q[..., None] + code * step[..., None]
    hi = jnp.sign(x) * mags
    lo = jnp.where(x > 0, dw_q[..., None] * 0.5, -dw_q[..., None] * 0.5)
    recon = jnp.where(absx >= dw_q[..., None], hi, lo)
    return recon, dw_q


def _sign_scales(dw_q: jnp.ndarray, G: int) -> jnp.ndarray:
    """Per-peer sign-plane weights for the uniform mean: dw_q_g / (2G)."""
    return (dw_q * (0.5 / G)).astype(jnp.float32)


def lo_plane(flat: jnp.ndarray, dw_q: jnp.ndarray) -> jnp.ndarray:
    """The low-resolution reconstruction plane ``sign(x) * dw_q/2``
    (sign(0) = -1, matching the packed sign-bit convention)."""
    half = dw_q[..., None] * 0.5
    return jnp.where(flat > 0, half, -half)


def signplane_weighted_aggregate(flat: jnp.ndarray, recons: jnp.ndarray,
                                 dw_q: jnp.ndarray,
                                 weights: jnp.ndarray) -> jnp.ndarray:
    """``sum_g weights_g * recons_g`` through the packed wire format.

    The single definition of the mixed-resolution aggregation identity
    (shared by the sim engine's rho-weighted user aggregation and the
    uniform cross-replica mean below): the 1-bit plane reduces inside
    the Pallas kernels with per-peer scales ``w_g * dw_q_g / 2``; the
    high-resolution correction ``recons - lo_plane`` — nonzero only on
    each peer's top-k support — rides a dense weighted reduce.
    """
    low = packed_sign_weighted_sum(
        flat, (weights * dw_q * 0.5).astype(jnp.float32))
    corr = jnp.einsum("g,gd->d", weights, recons - lo_plane(flat, dw_q))
    return low + corr


def aggregate_flat_stacked(flat: jnp.ndarray, comp: CompressorConfig,
                           segments: Optional[Tuple] = None
                           ) -> jnp.ndarray:
    """[G, d] per-replica flat deltas -> [d] compressed mean (GSPMD).

    ``segments``: optional per-layer budget segments tiling [0, d) —
    each runs this same aggregation with its own (s_budget, bits)."""
    flat = flat.astype(jnp.float32)
    G, d = flat.shape
    if comp.kind == "none":
        return jnp.mean(flat, axis=0)
    wp = comp.resolved_wire()
    if segments:
        return jnp.concatenate([
            aggregate_flat_stacked(flat[:, seg.start:seg.start + seg.size],
                                   _segment_comp(comp, wp, seg))
            for seg in segments])
    weights = jnp.full((G,), 1.0 / G, jnp.float32)
    if wp.plane == "packed":
        check_packed_dim(d, where="the packed dist exchange")
        # quantize-to-wire without a dense reconstruction: top-k picks
        # the per-replica anchor, the emit pass packs the wire planes,
        # and the decode+mean runs fused from the packed buffers
        k = budget_k(d, comp.s_budget)
        inf, dw_q = _rank_k_values(jnp.abs(flat), k, comp.exact_topk)
        wire = mixed_res_encode_anchored(flat, inf, dw_q, comp.bits,
                                         path=wp)
        if wp.checksum:
            # decode-side integrity check (DESIGN.md §14): a replica
            # whose packed planes fail the xor-fold word is masked out
            # of the mean with renormalized weights; all-valid leaves
            # the weights bit-for-bit untouched
            from repro.resilience.guards import quarantine_weights
            from repro.kernels.ops import verify_wire
            weights, _ = quarantine_weights(weights, verify_wire(wire))
        return mixed_res_wire_reduce(wire, weights, comp.bits, d,
                                     path=wp)
    recon, dw_q = mixed_recon(flat, comp)
    return signplane_weighted_aggregate(flat, recon, dw_q, weights)


def _ring_wire_reduce(wire, comp: CompressorConfig, wp: WirePath,
                      d: int, axes: Tuple[str, ...],
                      axis_sizes: Optional[Mapping[str, int]]
                      ) -> jnp.ndarray:
    """Ring-reduce the packed wire exchange: G-1 ``ppermute`` hops move
    each peer's packed buffers around the ring, and every hop folds the
    arriving planes into the local [d] accumulator via the chunked
    ``mixed_res_wire_reduce(acc=...)`` — exactly ONE peer's packed
    buffer is resident per hop, so the gathered [G, ...] plane stack
    (let alone a dense [G, d]) never exists.

    Each shard folds the peers in its own rotated ring order, so shards
    agree only to float32 roundoff (ulps), not bitwise — the documented
    reassociation tradeoff of DESIGN.md §12; reduce="gather" keeps the
    order-identical fold.  ``wire``: this shard's planes with leading
    axis 1."""
    if len(axes) != 1:
        raise ValueError(
            f"ring reduce runs over exactly one mesh axis, got {axes}")
    if axis_sizes is None or axes[0] not in axis_sizes:
        raise ValueError(
            "ring reduce needs the static group size: pass "
            f"axis_sizes={{{axes[0]!r}: <size>}} (jax cannot query an "
            "axis size inside a manual shard_map region)")
    G = int(axis_sizes[axes[0]])
    w1 = jnp.full((1,), 1.0 / G, jnp.float32)

    def hop_weight(hop_wire):
        # checksum verified AFTER transport, per hop: a corrupted
        # traveling buffer contributes weight 0 and the final fold
        # renormalizes over surviving peers (bit-neutral when all pass)
        if not wp.checksum:
            return w1, jnp.ones((), jnp.float32)
        from repro.kernels.ops import verify_wire
        ok = verify_wire(hop_wire)
        return jnp.where(ok, w1, 0.0), ok.astype(jnp.float32)[0]

    w_eff, good = hop_weight(wire)
    acc = mixed_res_wire_reduce(wire, w_eff, comp.bits, d, path=wp)
    perm = [(i, (i + 1) % G) for i in range(G)]
    traveling = wire
    for _ in range(G - 1):
        traveling = jax.tree_util.tree_map(
            lambda x: jax.lax.ppermute(x, axes[0], perm), traveling)
        w_eff, ok = hop_weight(traveling)
        good = good + ok
        acc = mixed_res_wire_reduce(traveling, w_eff, comp.bits, d,
                                    acc=acc, path=wp)
    if wp.checksum:
        scale = jnp.float32(G) / jnp.maximum(good, 1.0)
        acc = jnp.where(good < G, acc * scale, acc)
    return acc


def aggregate_flat_manual(flat: jnp.ndarray, comp: CompressorConfig,
                          axis_names: Sequence[str],
                          axis_sizes: Optional[Mapping[str, int]] = None,
                          segments: Optional[Tuple] = None
                          ) -> jnp.ndarray:
    """[d_local] replica-local flat delta -> [d_local] compressed mean
    over the named (manual) mesh axes.  Call inside shard_map.

    ``axis_sizes`` maps axis name -> static group size; required only
    by the ring reduce (``WirePath(reduce="ring")``), which cannot
    query the axis size inside the manual region.  ``segments``: see
    :func:`aggregate_flat_stacked` (validate() rejects ring+budget)."""
    flat = flat.astype(jnp.float32)
    axes = tuple(axis_names)
    if comp.kind == "none":
        return jax.lax.pmean(flat, axes)
    d = flat.shape[0]
    wp = comp.resolved_wire()
    if segments:
        return jnp.concatenate([
            aggregate_flat_manual(flat[seg.start:seg.start + seg.size],
                                  _segment_comp(comp, wp, seg),
                                  axes, axis_sizes)
            for seg in segments])
    if wp.plane == "packed":
        check_packed_dim(d, where="the packed dist exchange")
        # encode the local shard to wire; the collective then moves
        # exactly the accounted wire payload (uint32 planes + 8-lane
        # header), never a dense [G, d] stack
        k = budget_k(d, comp.s_budget)
        inf, dw_q = _rank_k_values(jnp.abs(flat), k, comp.exact_topk)
        wire = mixed_res_encode_anchored(flat[None], inf[None],
                                         dw_q[None], comp.bits, path=wp)
        if wp.reduce == "ring":
            return _ring_wire_reduce(wire, comp, wp, d, axes, axis_sizes)
        # gather: one all_gather of the packed buffers, one fused
        # decode+mean over all G peers
        local = jax.tree_util.tree_map(lambda x: x[0], wire)
        g_wire = jax.lax.all_gather(local, axes)
        G = g_wire.head.shape[0]
        weights = jnp.full((G,), 1.0 / G, jnp.float32)
        if wp.checksum:
            # verified after the gather moved the planes (DESIGN.md §14)
            from repro.resilience.guards import quarantine_weights
            from repro.kernels.ops import verify_wire
            weights, _ = quarantine_weights(weights,
                                            verify_wire(g_wire))
        return mixed_res_wire_reduce(g_wire, weights, comp.bits, d,
                                     path=wp)
    recon, dw_q = mixed_recon(flat, comp)
    from repro.kernels.ops import _interpret, sign_pad_len
    from repro.kernels.quant_pack import sign_dequant_reduce, signpack
    interp = _interpret(None)
    d_pad = sign_pad_len(d)
    padded = jnp.pad(flat, (0, d_pad - d)) if d_pad != d else flat
    words = signpack(padded.reshape(-1, 128), interpret=interp)  # [W, 4]
    g_words = jax.lax.all_gather(words, axes)                    # [G, W, 4]
    g_dwq = jax.lax.all_gather(dw_q, axes)                       # [G]
    G = g_words.shape[0]
    low = sign_dequant_reduce(g_words, _sign_scales(g_dwq, G),
                              interpret=interp)
    low = low.reshape(-1)[:d]
    corr = jax.lax.pmean(recon - lo_plane(flat, dw_q), axes)
    return low + corr


def aggregate_delta(deltas: Any, specs: Any, axis_names: Sequence[str],
                    comp: CompressorConfig,
                    axis_sizes: Optional[Mapping[str, int]] = None
                    ) -> Tuple[Any, Dict[str, Any]]:
    """Compressed cross-replica mean of a delta pytree.

    deltas:     pytree of per-replica deltas.  With ``axis_names``
                empty, every leaf carries a leading replica axis
                ``[G, ...]`` (stacked/GSPMD mode); with ``axis_names``
                given, leaves are replica-local shards and the call
                must be inside a shard_map manual over those axes.
    specs:      pytree of PartitionSpecs matching ``deltas`` (leaf
                layout over the non-replica mesh axes).  Kept for the
                wire-format record and future re-constraint; the
                arithmetic does not depend on it.
    axis_names: mesh axes to aggregate over (manual mode), or () / None.
    comp:       CompressorConfig.
    axis_sizes: axis name -> static group size, required only for the
                ring reduce in manual mode (see aggregate_flat_manual).

    Returns ``(aggregated, info)`` where ``aggregated`` mirrors
    ``deltas`` without the replica axis (stacked mode) / shard-local
    (manual mode), in float32, and ``info`` carries the static payload
    accounting: ``wire_bits_per_replica`` is the exact number of bits
    one replica puts on the wire per round (fp32 everything for
    ``none``; packed sign+idx+code planes for ``mixed``).
    ``kind="none"`` reproduces the fp32 mean bit-exactly.
    """
    comp.validate()
    del specs  # layout record only — see docstring
    leaves, treedef = jax.tree_util.tree_flatten(deltas)
    if not leaves:
        return deltas, {"wire_bits_per_replica": 0, "d": 0, "k": 0}
    manual = bool(axis_names)
    # per-layer budget (DESIGN.md §13): resolve the leaf-group segments
    # against the delta tree itself — stacked leaves carry a leading
    # replica axis the offsets must skip
    budget = getattr(comp.resolved_wire(), "effective_budget", None)
    segments = None
    if budget is not None:
        segments = budget.segments_for(
            deltas, default_lambda=0.0, default_b=comp.bits,
            default_s=comp.s_budget,
            skip_leading=0 if manual else 1)
    if manual:
        sizes = [int(leaf.size) for leaf in leaves]
        flat = jnp.concatenate(
            [leaf.reshape(-1).astype(jnp.float32) for leaf in leaves])
        agg = aggregate_flat_manual(flat, comp, axis_names, axis_sizes,
                                    segments=segments)
    else:
        G = leaves[0].shape[0]
        sizes = [int(leaf.size) // G for leaf in leaves]
        flat = jnp.concatenate(
            [leaf.reshape(G, -1).astype(jnp.float32) for leaf in leaves],
            axis=1)
        agg = aggregate_flat_stacked(flat, comp, segments=segments)
    d = int(sum(sizes))
    out_leaves = []
    off = 0
    for leaf, n in zip(leaves, sizes):
        shape = leaf.shape[1:] if not manual else leaf.shape
        out_leaves.append(agg[off:off + n].reshape(shape))
        off += n
    info = {
        "wire_bits_per_replica": payload_bits(d, comp, segments),
        "d": d,
        "k": budget_k(d, comp.s_budget) if comp.kind == "mixed" else 0,
    }
    if segments:
        info["segment_bits"] = tuple(
            wire_bits(seg.size, budget_k(seg.size, seg.s_budget), seg.b)
            for seg in segments)
        info["segments"] = segments
    return jax.tree_util.tree_unflatten(treedef, out_leaves), info
