"""Vectorized FL-over-CFmMIMO engine — all K users in one dispatch.

The legacy loop (repro.fl.loop.run_fl_sequential) trains users one at a
time: per round it pays K jit dispatches for the local AdaGrad runs
plus K eager op-by-op quantizer calls, so wall-clock at the paper's
K=20/40 is dominated by dispatch overhead, not compute.  This engine
stacks the per-user minibatches to [K, L, b, ...] and runs the local
training of ALL users as one vmapped, jit-compiled step, followed by
one batched (vmapped) quantizer call on the stacked [K, d] deltas.

Execution modes (EngineConfig):

* exact (``fused=False``, default — what run_fl delegates to): the K
  local AdaGrad runs + delta flattening are a single jit dispatch;
  quantization and the rho-weighted aggregation then replay the
  sequential loop's eager op-for-op arithmetic in the same order.
  Round logs (params, bits, latency, accuracy) reproduce
  run_fl_sequential BIT-FOR-BIT at fixed seed (asserted by
  tests/test_sim_engine.py).  Fusing quantization into the same XLA
  graph would contract mul+add chains into FMAs and drift from the
  eager reference by 1 ulp per op — measured, and why this mode keeps
  quantize/aggregate eager.
* fused (``fused=True`` — what the scenario sweeps run): train,
  batched quantize, aggregation and the model update compile into ONE
  jit step per round.  Fastest path; equals the exact mode to float32
  roundoff (cross-op FMA contraction), not bit-for-bit.
* ``aggregation="signplane"`` (implies fused) — the fused step routes
  the low-resolution plane of the mixed-resolution scheme through the
  Pallas wire-format kernels: every user's delta sign plane is
  bit-packed with ``signpack`` ([W,128] f32 -> [W,4] uint32) and the
  rho*dw_q/2-weighted multi-user reduction runs in
  ``sign_dequant_reduce`` — the packed uint32 planes a real multi-peer
  aggregation would move — plus a dense correction on the (sparse)
  high-resolution support.  Exercises the wire format end-to-end
  instead of only in unit tests.
* ``aggregation="wire"`` (implies fused) — the full fused
  quantize-to-wire path (kernels/mixed_res.py, DESIGN.md section 9):
  the per-user quantization reductions, the packed sign/hi/code wire
  planes and the rho-weighted multi-user dequantize+reduce all run in
  the streaming mixed-resolution kernel suite, and the dense per-user
  reconstructions are never materialized.  Payload bits and the aux
  diagnostics replay the reference accounting exactly; the aggregated
  update agrees with the fused dense path to a documented ulp bound.

Beyond the paper's fixed setting the engine simulates per-round user
churn (partial participation with re-normalized aggregation weights and
frozen quantizer state for absent users) and Monte-Carlo channel
redraws (fresh large-scale realization every ``redraw_channel_every``
rounds) — see repro.sim.scenarios for the named workloads.

Replicated mode (the Monte-Carlo replicate axis, DESIGN.md section 8):
``start_replicated_run(R)`` / ``train_round_replicated`` run R
independent FL trajectories of the SAME problem — distinct minibatch
RNG streams, distinct participation draws, distinct channel
realizations, independently evolving quantizer states — with the whole
per-round device step vmapped over a leading R axis, so one jitted
dispatch per round trains all R trajectories.  R = 1 routes through
the IDENTICAL compiled step as the unreplicated path (no vmap), which
is what makes the replicate-parity suite's bit-for-bit claim possible.

Asynchronous mode (``EngineConfig(async_mode=True, staleness=...)``,
DESIGN.md section 11): per-user upload-completion times from the power
solve become a scheduling fact instead of a latency footnote.  Each
round the server waits only until a deadline (fixed seconds or a
quantile of the pending completion times), aggregates the uploads that
arrived with staleness weights ``rho_j (1+staleness_j)^-alpha``
renormalized into a convex combination, and parks the stragglers'
payloads in a bounded-staleness buffer (at most one in-flight upload
per user; dropped once ``staleness > max_staleness`` or when the user
churns out mid-upload).  The per-round device work stays two jitted
dispatches — one train+quantize call producing the fresh payloads
(dense [K, d] recons or packed MixedResWire planes) and one
aggregate+buffer-shuffle call — so the replicate axis and the fused
Pallas wire path keep working unchanged.  The host event clock between
them is pure numpy (``advance_async_clock``).

Public API / invariants:

* ``VectorizedFLEngine(...).run()`` — one-call driver; or the
  round-stepping quartet ``start_run`` / ``train_round`` /
  ``solve_uplink_host`` (returns an :class:`UplinkSolution`; the
  ``_detailed`` spelling is a deprecated alias) / ``finish_round``
  (async inserts ``complete_round_async`` between solve and finish —
  aggregation happens there, never in ``finish_round``).

Streaming cohorts (``EngineConfig(wire=WirePath(cohort_size=C))``,
DESIGN.md section 12): the fused packed-plane step scans the K users
in cohorts of C — each scan iteration trains C users, encodes their
packed wire planes and folds the weighted dequant-reduce into a
carried [d] accumulator, so the dense [K, d] gradient matrix never
exists at any fan-in and device residency scales with C, not K.
``cohort_size=None`` keeps today's fully vectorized step bit-for-bit.
``WirePath(clusters=N)`` adds the two-level hierarchy: contiguous
AP-cluster user groups aggregate into partial [d] planes on device
(only one cluster's minibatches resident at a time), combined
host-ordered before a single param update.
* Replicated: ``start_replicated_run(R)`` / ``train_round_replicated``
  (+ ``complete_round_replicated_async``); R=1 is bit-for-bit the
  unreplicated path (same compiled step, squeezed).
* ``async_mode=True`` with a sync StalenessConfig (no deadline — the
  "alpha=0, infinite deadline" reduction) runs EXACTLY the lockstep
  code path: bit-for-bit with async_mode=False by construction
  (tests/test_async_engine.py pins it).
* Sync mode never reads the async fields; all pre-async call sites
  keep their behavior bit-for-bit.
"""
from __future__ import annotations

import dataclasses
import warnings
import weakref
from typing import List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.channel import (ChannelRealization, computation_latency,
                                make_channel)
from repro.core.power.base import PowerController
from repro.core.quantize import Quantizer
from repro.core.quantize.base import flatten_pytree, unflatten_pytree
from repro.core.quantize.layer_budget import segmented_quantize
from repro.data.federated import user_fractions, validate_shards
from repro.data.synthetic import ImageDataset
# the mixed-resolution signplane aggregation identity (packed 1-bit
# reduce + dense correction on the top-k support) has ONE definition,
# shared with repro.dist's cross-replica aggregation
from repro.dist.compressor import \
    signplane_weighted_aggregate as _signplane_aggregate
from repro.kernels import WirePath, check_packed_dim, from_aggregation
from repro.kernels.ops import (H_DBAR, H_DWQ, H_INF, MixedResWire,
                               mixed_res_encode, mixed_res_wire_reduce,
                               segmented_wire_aggregate)
from repro.kernels.ops import mixed_res_wire_aggregate as _wire_aggregate
from repro.resilience import guards as _rg
from repro import obs as _obs


@dataclasses.dataclass(frozen=True)
class StalenessConfig:
    """Async round-deadline + staleness-weighting policy.

    The server closes a round at ``min(deadline, time all pending
    uploads complete)`` where the deadline is either ``deadline_s``
    (fixed seconds) or the ``deadline_quantile`` of this round's
    pending completion times (fresh uploads' solve latencies plus
    in-flight uploads' remaining times).  Exactly one of the two may
    be set; with BOTH unset the config is "sync" (infinite deadline:
    every round waits for its slowest upload — today's lockstep) and
    ``EngineConfig.async_active`` stays False even under
    ``async_mode=True``, which is the bit-for-bit sync reduction the
    parity test pins.

    Arrivals are averaged with weights ``rho_j (1+staleness_j)^-alpha``
    renormalized to a convex combination (``staleness_weights``);
    ``alpha=0`` weighs stale and fresh uploads alike.  A missed upload
    waits in the buffer at most ``max_staleness`` rounds
    (``max_staleness=0`` disables buffering: misses are dropped
    outright).
    """
    deadline_s: Optional[float] = None
    deadline_quantile: Optional[float] = None
    alpha: float = 0.0
    max_staleness: int = 2

    def __post_init__(self):
        if self.deadline_s is not None and self.deadline_quantile is not None:
            raise ValueError("set deadline_s OR deadline_quantile, not both")
        if self.deadline_s is not None and self.deadline_s <= 0:
            raise ValueError(f"deadline_s must be > 0, got {self.deadline_s}")
        if self.deadline_quantile is not None and not (
                0.0 < self.deadline_quantile <= 1.0):
            raise ValueError("deadline_quantile must be in (0, 1], got "
                             f"{self.deadline_quantile}")
        if self.alpha < 0:
            raise ValueError(f"alpha must be >= 0, got {self.alpha}")
        if self.max_staleness < 0:
            raise ValueError(
                f"max_staleness must be >= 0, got {self.max_staleness}")

    @property
    def is_sync(self) -> bool:
        """No finite deadline configured — the lockstep reduction."""
        return (self.deadline_s is None or np.isinf(self.deadline_s)) \
            and self.deadline_quantile is None


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Engine-level knobs beyond the paper's Algorithm 1."""
    # DEPRECATED spelling of the wire-path plane — "dense" |
    # "signplane" | "wire".  New call sites set ``wire=WirePath(...)``
    # instead; the legacy strings keep working through
    # repro.kernels.from_aggregation (DeprecationWarning).
    aggregation: str = "dense"
    # The unified wire-path spec (repro.kernels.WirePath): which plane
    # moves at the fan-in, which lowering runs it, and the streaming
    # knobs (cohort_size — scan the K users in cohorts so no [K, d]
    # buffer ever exists; clusters — two-level AP-cluster hierarchy).
    # None defers to the legacy ``aggregation`` string; setting BOTH a
    # non-default aggregation and wire is an error.
    wire: Optional[WirePath] = None
    # fused=False (exact mode): only the K local AdaGrad runs share one
    # jit dispatch; quantization and aggregation replay the sequential
    # loop's eager per-op arithmetic — BIT-FOR-BIT equal to
    # run_fl_sequential.  fused=True (production mode): train, batched
    # quantize, aggregate and model update compile into ONE jit step
    # per round; XLA's cross-op fusion (FMA contraction etc.) makes it
    # equal to the exact mode only to float32 roundoff.
    # aggregation="signplane" always runs fused.
    fused: bool = False
    # How the K users' local AdaGrad runs are batched inside the single
    # jitted step.  "map" (lax.map) compiles the per-user graph once and
    # loops it on-device — on CPU the per-user convs hit the fast
    # unbatched lowering (vmap turns them into grouped convs, measured
    # ~3x slower there).  "vmap" batches all users' convs into one
    # grouped launch — the right choice on TPU/GPU.  Both are bitwise
    # identical to the sequential per-user jit.
    local_batching: str = "map"      # "map" | "vmap"
    # How the Monte-Carlo replicate axis R is batched inside the single
    # jitted replicated step.  "vmap" batches all R trajectories' convs
    # together — right on TPU/GPU; on CPU it hits the same slow
    # grouped-conv lowering as local_batching="vmap", so "auto"
    # (default) picks "map" (lax.map: compile the per-replicate graph
    # once, loop it on-device — still ONE dispatch per round) on CPU
    # and "vmap" on accelerators.  aggregation="signplane"/"wire"
    # always run "map": the Pallas wire kernels expect their unbatched
    # windows.
    replicate_batching: str = "auto"  # "auto" | "map" | "vmap"
    participation: float = 1.0       # P(user active in a round) — churn
    redraw_channel_every: int = 0    # 0 = fixed realization (paper)
    channel_seed: int = 0            # base seed for Monte-Carlo redraws
    # Optional jax Mesh with a "data" axis: the user axis K of every
    # stacked array (minibatches, deltas, quantizer state) is laid over
    # it, so one engine step scales the K users across devices — the
    # sweep-layer counterpart of repro.dist's replica sharding.  None =
    # single-device (default); ignored with a warning unless the
    # data-axis size divides K evenly.
    mesh: Optional[object] = None
    # Round logging.  Every finished round is emitted to the active
    # repro.obs session (no-op without one); verbose=True additionally
    # prints the quickstart's per-eval-round console line (same as
    # run(verbose=True)), throttled to every log_every-th eval round.
    verbose: bool = False
    log_every: int = 1
    # Asynchronous rounds (DESIGN.md section 11): per-user upload
    # completion times govern aggregation.  async_mode=True with a
    # sync StalenessConfig (no deadline) runs the lockstep code path
    # unchanged — see async_active.
    async_mode: bool = False
    staleness: StalenessConfig = dataclasses.field(
        default_factory=StalenessConfig)
    # Optional repro.resilience.ResilienceConfig: threads seeded
    # per-round fault masks through the fused step and arms the
    # jit-safe quarantine guards (DESIGN.md §14).  None (default)
    # builds the exact pre-resilience step graphs; a config with
    # FaultPlan.none() injects nothing and is bit-for-bit with None
    # (tests/test_resilience.py parity battery).
    resilience: Optional[object] = None

    @property
    def effective_fused(self) -> bool:
        if self.wire is not None:
            return self.fused or self.wire.plane != "dense"
        return self.fused or self.aggregation in ("signplane", "wire")

    def wire_path(self) -> WirePath:
        """The resolved WirePath: ``wire`` when set, else the legacy
        ``aggregation`` string mapped through its deprecation shim
        (silently for the "dense" default)."""
        if self.wire is not None:
            if self.aggregation != "dense":
                raise ValueError(
                    "set EngineConfig.wire OR the legacy aggregation "
                    f"string, not both (wire={self.wire!r}, "
                    f"aggregation={self.aggregation!r})")
            return self.wire
        return from_aggregation(self.aggregation,
                                warn=self.aggregation != "dense")

    @property
    def async_active(self) -> bool:
        """True only when async machinery actually engages: async_mode
        AND a finite deadline.  ``async_mode=True`` with the default
        (sync) StalenessConfig reduces to today's lockstep engine
        bit-for-bit because this property gates EVERY async branch."""
        return self.async_mode and not self.staleness.is_sync


def _subchannel(chan: ChannelRealization, idx: np.ndarray
                ) -> ChannelRealization:
    """Restrict a realization to the active-user subset: inactive users
    neither transmit (no power allocated, no interference) nor count
    toward the straggler latency.

    The batched phy path (repro.phy solvers with a 0/1 ``mask``)
    implements these same semantics device-side; equivalence is pinned
    by tests/test_phy_parity.py and tests/test_phy_driver.py.
    """
    cfg = dataclasses.replace(chan.cfg, K=len(idx))
    return dataclasses.replace(
        chan, cfg=cfg, beta=chan.beta[:, idx], pilot=chan.pilot[idx],
        gamma=chan.gamma[:, idx], A_bar=chan.A_bar[idx],
        B_bar=chan.B_bar[idx], B_tilde=chan.B_tilde[np.ix_(idx, idx)],
        I_M=chan.I_M[idx])


# ------------------------------------------------- async event clock
def staleness_weights(rho: np.ndarray, staleness: np.ndarray,
                      arrived: np.ndarray, alpha: float) -> np.ndarray:
    """Normalized aggregation weights ``rho_j (1+s_j)^-alpha`` over the
    arrived set — a convex combination (non-negative, sums to 1 per
    leading-batch row) whenever any upload arrived, all-zero otherwise.

    rho: [K]; staleness/arrived: [..., K] (staleness in rounds, 0 for
    fresh uploads).  Pure numpy — the hypothesis property battery in
    tests/test_async_engine.py exercises it directly.
    """
    arr = np.asarray(arrived, bool)
    raw = (np.asarray(rho, np.float64)
           * (1.0 + np.asarray(staleness, np.float64)) ** (-float(alpha))
           * arr)
    tot = raw.sum(axis=-1, keepdims=True)
    return np.divide(raw, tot, out=np.zeros_like(raw), where=tot > 0)


def straggler_gap(per_user_s: np.ndarray, mask: np.ndarray) -> float:
    """Slowest-minus-median upload completion time over ``mask`` users
    — the round's straggler gap (0 when fewer than one uploader)."""
    lat = np.asarray(per_user_s, np.float64)[np.asarray(mask) > 0]
    if lat.size == 0:
        return 0.0
    return float(np.max(lat) - np.median(lat))


class AsyncClockStep(NamedTuple):
    """One ``advance_async_clock`` transition.  All arrays [B, K]
    unless noted; B is the replicate axis (1 unreplicated)."""
    round_s: np.ndarray            # [B] event-clock round duration
    arrived: np.ndarray            # bool — aggregated this round
    w_fresh: np.ndarray            # weights of arrived FRESH uploads
    w_buf: np.ndarray              # weights of arrived BUFFERED uploads
    move: np.ndarray               # fresh upload missed -> enters buffer
    keep: np.ndarray               # buffered upload missed -> stays
    in_flight: np.ndarray          # next round's busy mask (move|keep)
    remaining_s: np.ndarray        # next round's remaining upload time
    staleness: np.ndarray          # next round's buffer staleness
    arrived_staleness: np.ndarray  # staleness of each arrival (0 fresh)
    dropped_stale: np.ndarray      # [B] uploads dropped: staleness bound
    dropped_churn: np.ndarray      # [B] uploads dropped: user churned out
    straggler_gap_s: np.ndarray    # [B] max - median pending completion


def advance_async_clock(in_flight: np.ndarray, remaining_s: np.ndarray,
                        staleness: np.ndarray, ell: np.ndarray,
                        fresh: np.ndarray, participating: np.ndarray,
                        rho: np.ndarray, cfg: StalenessConfig
                        ) -> AsyncClockStep:
    """Pure host event-clock transition for one async round.

    Inputs are [B, K]: ``in_flight``/``remaining_s``/``staleness`` the
    buffer state, ``ell`` this round's per-user solve latencies (fresh
    uploads), ``fresh`` the fresh-uploader mask and ``participating``
    the churn mask.  Semantics:

    * an in-flight upload whose user churned out is dropped — a user
      who drops mid-upload must never be aggregated;
    * the round closes at ``min(deadline, max pending completion)`` —
      with every pending upload inside the deadline this equals the
      lockstep straggler latency;
    * arrivals (completion <= round_s) are weighted by
      ``staleness_weights``; misses enter/stay in the buffer with
      ``remaining_s`` decremented by the elapsed round and staleness
      bumped, dropped once ``staleness > cfg.max_staleness``.
    """
    part = np.asarray(participating) > 0
    fresh = np.asarray(fresh) > 0
    churn_drop = in_flight & ~part
    busy = in_flight & part
    cand = np.where(fresh, np.asarray(ell, np.float64), np.inf)
    cand = np.where(busy, remaining_s, cand)
    pending = fresh | busy
    B = cand.shape[0]
    round_s = np.zeros(B)
    gap = np.zeros(B)
    for b in range(B):
        pc = cand[b][pending[b]]
        if pc.size == 0:
            continue
        if cfg.deadline_s is not None:
            deadline = float(cfg.deadline_s)
        else:
            deadline = float(np.quantile(pc, cfg.deadline_quantile))
        # a server that saw every pending upload land early closes the
        # round then — deadline_s=inf therefore reduces to lockstep
        round_s[b] = min(deadline, float(pc.max()))
        gap[b] = float(pc.max() - np.median(pc))
    arrived = pending & (cand <= round_s[:, None])
    arr_stale = np.where(busy, staleness, 0)
    w = staleness_weights(rho, arr_stale, arrived, cfg.alpha)
    # misses: fresh ones enter the buffer at staleness 1 (dropped
    # outright when max_staleness == 0); buffered ones age one round
    miss_fresh = fresh & ~arrived
    miss_buf = busy & ~arrived
    stale_drop = miss_buf & (staleness + 1 > cfg.max_staleness)
    keep = miss_buf & ~stale_drop
    move = miss_fresh if cfg.max_staleness >= 1 \
        else np.zeros_like(miss_fresh)
    elapsed = round_s[:, None]
    return AsyncClockStep(
        round_s=round_s, arrived=arrived,
        w_fresh=w * (fresh & arrived), w_buf=w * (busy & arrived),
        move=move, keep=keep, in_flight=move | keep,
        remaining_s=np.where(move, cand - elapsed,
                             np.where(keep, remaining_s - elapsed, 0.0)),
        staleness=np.where(move, 1, np.where(keep, staleness + 1, 0)),
        arrived_staleness=np.where(arrived, arr_stale, 0),
        dropped_stale=(stale_drop | (miss_fresh & ~move)).sum(axis=-1),
        dropped_churn=churn_drop.sum(axis=-1),
        straggler_gap_s=gap)


@dataclasses.dataclass
class AsyncClock:
    """Mutable async buffer state threaded through a run.

    Host arrays are [B, K] (B = 1 unreplicated, else R); ``buffer``
    holds the parked device payloads — dense [(B,) K, d] recons or
    stacked MixedResWire planes — aligned slot-per-user (at most one
    in-flight upload per user).  ``payload`` stages the current
    round's fresh device payload between ``train_round`` and
    ``complete_round_async``."""
    in_flight: np.ndarray
    remaining_s: np.ndarray
    staleness: np.ndarray
    buffer: object
    payload: object = None
    uploads_started: int = 0
    arrived_total: int = 0
    dropped_stale: int = 0
    dropped_churn: int = 0


@dataclasses.dataclass
class AsyncRoundInfo:
    """Per-round async accounting (arrays [B]; B = 1 unreplicated)."""
    round_uplink_s: np.ndarray     # event-clock round duration
    n_arrived: np.ndarray          # arrivals aggregated this round
    mean_staleness: np.ndarray     # mean staleness over arrivals
    max_staleness_obs: np.ndarray  # max staleness over arrivals
    straggler_gap_s: np.ndarray    # max - median pending completion
    dropped_stale: np.ndarray
    dropped_churn: np.ndarray
    effective_participation: np.ndarray   # n_arrived / K
    in_flight_next: np.ndarray     # buffer occupancy entering next round


class UplinkSolution(NamedTuple):
    """Structured result of the uplink power solve (stage 3).

    A NamedTuple so the legacy ``straggler_s, per_user_s = solve...``
    unpacking keeps working; ``latencies`` is always populated ([K]
    per-user upload-completion times, 0 for absent users — the async
    event clock's input).  The batched driver's replicated variant
    carries [R, K]."""
    straggler_s: float
    latencies: np.ndarray


@dataclasses.dataclass
class RoundWork:
    """What one training round hands to the power-control stage.

    In async mode ``active`` is the FRESH-uploader mask (participating
    and not mid-upload — the users whose payloads this round's power
    solve carries) and ``participating`` the raw churn mask; in sync
    mode they coincide and ``participating`` stays None."""
    t: int
    bits_np: np.ndarray            # [K] payload bits; 0 for absent users
    active: np.ndarray             # [K] 0/1 participation mask
    mean_s: float                  # mean high-res fraction (active users)
    participating: Optional[np.ndarray] = None   # [K] churn mask (async)
    quarantined: int = 0           # users masked out by the guards


@dataclasses.dataclass
class ReplicatedRoundWork:
    """RoundWork with a leading Monte-Carlo replicate axis R."""
    t: int
    bits_np: np.ndarray            # [R, K] payload bits; 0 for absent users
    active: np.ndarray             # [R, K] 0/1 participation masks
    mean_s: np.ndarray             # [R] mean high-res fraction per replicate
    participating: Optional[np.ndarray] = None   # [R, K] churn masks (async)
    quarantined: Optional[np.ndarray] = None     # [R] guard-masked users


@dataclasses.dataclass
class RunState:
    """Mutable per-run state for the round-stepping API.

    ``run()`` drives it with the host power solve; the batched grid
    driver (repro.sim.phy_driver) steps many engines' states in
    lockstep and supplies uplink latencies from ONE batched phy solve
    per round.
    """
    params: object
    qstate: object
    chan: Optional[ChannelRealization]
    rng: np.random.Generator
    part_rng: np.random.Generator
    test_x: object
    test_y: object
    logs: List
    cum_latency: float = 0.0
    rounds_done: int = 0
    async_clock: Optional[AsyncClock] = None


@dataclasses.dataclass
class ReplicatedRunState:
    """Per-run state for R vmapped Monte-Carlo replicates.

    Device arrays carry a leading R axis (params/qstate pytrees);
    host-side RNG streams and channel realizations are per-replicate
    lists.  Latency accounting is NOT here — the replicated grid
    driver (repro.sim.phy_driver) owns it per (cell, replicate), since
    one training state serves many power cells.
    """
    params: object                          # [R]-stacked param pytree
    qstate: object                          # [R, K, ...] stacked (or None)
    chans: List[Optional[ChannelRealization]]   # length R
    rngs: List[np.random.Generator]             # minibatch streams
    part_rngs: List[np.random.Generator]        # churn streams
    test_x: object
    test_y: object
    rounds_done: int = 0
    async_clock: Optional[AsyncClock] = None

    @property
    def R(self) -> int:
        return len(self.rngs)


# RNG-stream folding for replicate r > 0 (replicate 0 keeps the
# unreplicated streams bit-for-bit — the parity contract):
# minibatches   default_rng((seed, _REPL_TAG, r))
# churn         default_rng((seed, 0x5EED, _REPL_TAG, r))
# channels      make_channel(seed = channel_seed + r * stride + t)
# The channel-seed stride keeps replicate streams disjoint from the
# unreplicated redraw seeds (channel_seed + t, t <= T << stride).
_REPL_TAG = 0x4D43                  # "MC"
_REPL_CHANNEL_SEED_STRIDE = 1 << 20

# ordinal for per-instance obs retrace-probe names: a grid builds one
# engine per quantizer and each one legitimately traces its step once,
# so probe counts must not aggregate across instances (a shared name
# would read as a retrace storm)
_ENGINE_ORDINAL = [0]

# device copies of training sets: (id(x), id(y), placement) -> (rows, y).
# Every engine built on the same ImageDataset with the same placement
# shares one copy (a grid holds one engine per quantizer on one
# problem); an entry goes when either host array is freed, and holds
# no reference to them.
_RESIDENT: dict = {}
_LANES = 128        # the minor tile of a TPU float32 array


def _resident_set(dataset: ImageDataset, sharding=None):
    """``dataset`` on the device once, uncommitted on the default
    device or with ``sharding``: its images as float32 rows, one sample
    per row, zero-padded to a multiple of ``_LANES``, and its labels as
    int32 (both converted as ``jnp.asarray`` converts them).

    The layout is for the TPU's tiled memory: as ``[N, H, W, C]`` it
    lays N out along the lanes and every gather first relays out the
    whole set, and a row that ends inside a tile makes it copy the set
    too; whole-tile rows are gathered in place.  Counts the bytes of
    each new copy in ``engine.resident_bytes``."""
    key = (id(dataset.x), id(dataset.y), sharding)
    hit = _RESIDENT.get(key)
    if hit is None:
        put = jnp.asarray if sharding is None else \
            (lambda a: jax.device_put(a, sharding))
        rows = np.reshape(dataset.x, (len(dataset.x), -1))
        pad = -rows.shape[1] % _LANES
        if pad:
            rows = np.pad(rows, ((0, 0), (0, pad)))
        hit = _RESIDENT[key] = (put(rows), put(dataset.y))
        for a in (dataset.x, dataset.y):
            weakref.finalize(a, _RESIDENT.pop, key, None)
        _obs.counter("engine.resident_bytes",
                     hit[0].nbytes + hit[1].nbytes)
    return hit


def _gather_minibatches(rows, y, sel, sample_shape):
    """``x[sel], y[sel]`` from the resident rows: a copy of the values,
    so the result is the host gather's, bit for bit."""
    n = int(np.prod(sample_shape))
    return (rows[sel][..., :n].reshape(sel.shape + sample_shape),
            y[sel])


_gather_default = jax.jit(_gather_minibatches, static_argnums=3)


class VectorizedFLEngine:
    """Algorithm 1 with all K users vectorized into one step per round.

    Drop-in engine behind :func:`repro.fl.run_fl`; also the substrate
    for the scenario sweeps in repro.sim.sweep.  The wireless part
    (power control, closed-form rates) stays on the host exactly as in
    the sequential loop.
    """

    def __init__(self, dataset: ImageDataset, test: ImageDataset,
                 shards: List[np.ndarray], model,
                 quantizer: Quantizer, power: Optional[PowerController],
                 chan: Optional[ChannelRealization], fl,
                 engine: Optional[EngineConfig] = None):
        # ``model``: a repro.fl.ModelSpec or (the historical signature)
        # a PaperCNNConfig.  Local import: repro.fl imports us.
        from repro.fl.models import as_model_spec

        self.model_spec = as_model_spec(model)
        self.cnn_cfg = self.model_spec.config   # legacy attribute
        self.engine_cfg = engine or EngineConfig()
        # one resolved WirePath drives every plane/lowering/streaming
        # decision below; the legacy aggregation string warns here once
        wp = self.engine_cfg.wire_path()
        self.wire_path_spec = wp
        self._plane = wp.plane
        self._cohort = wp.cohort_size
        self._clusters = wp.clusters
        if self.engine_cfg.local_batching not in ("map", "vmap"):
            raise ValueError(
                f"unknown local_batching {self.engine_cfg.local_batching!r}")
        if self.engine_cfg.replicate_batching not in ("auto", "map",
                                                      "vmap"):
            raise ValueError(f"unknown replicate_batching "
                             f"{self.engine_cfg.replicate_batching!r}")
        if (self._plane in ("signplane", "packed")
                and quantizer.name != "mixed-resolution"):
            raise ValueError(
                f"the {self._plane} wire plane packs the "
                "mixed-resolution wire format; quantizer "
                f"{quantizer.name!r} has none")
        if self._plane == "packed" and quantizer.b > 16:
            raise ValueError(
                "the wire kernels store magnitude codes in <= 16 bits; "
                f"got b={quantizer.b}")
        if self.engine_cfg.async_active:
            if not self.engine_cfg.effective_fused:
                raise ValueError(
                    "async rounds split the fused step into train and "
                    "aggregate dispatches; configure "
                    "EngineConfig(fused=True)")
            if self._plane == "signplane":
                raise ValueError(
                    "async rounds buffer packed payloads; use the "
                    "'packed' plane (full wire format) or 'dense'")
            if wp.streaming:
                raise ValueError(
                    "async rounds buffer full-K payload slots; cohort "
                    "streaming (WirePath.cohort_size) is lockstep-only")
            if self.engine_cfg.mesh is not None:
                warnings.warn(
                    "EngineConfig.mesh user-axis sharding is not "
                    "supported in async mode; running unsharded",
                    stacklevel=2)
        if wp.streaming and self.engine_cfg.mesh is not None:
            warnings.warn(
                "EngineConfig.mesh user-axis sharding is not supported "
                "with cohort streaming; running unsharded", stacklevel=2)

        self.dataset, self.test = dataset, test
        self.shards = shards
        self.quantizer, self.power, self.chan, self.fl = \
            quantizer, power, chan, fl
        self.K = len(shards)
        validate_shards(shards)   # empty shard -> clear error, not take=0
        # uniform minibatch size so user batches stack to [K, L, b];
        # identical to the sequential loop whenever every shard holds at
        # least batch_size samples (the benchmarks' regime)
        self.take = min(fl.batch_size, min(len(s) for s in shards))
        if self.take < fl.batch_size:
            warnings.warn(
                f"smallest shard ({self.take} samples) < batch_size "
                f"({fl.batch_size}): the engine's uniform [K, L, b] "
                f"stacking trains EVERY user with batch {self.take} "
                "(the sequential loop clamps per user; run_fl falls "
                "back to it in this case)", stacklevel=2)
        self.rho = user_fractions(shards)

        self.params = self.model_spec.init(jax.random.PRNGKey(fl.seed))
        flat0, self.spec = flatten_pytree(self.params)
        self.d = int(flat0.size)
        if self._plane == "packed":
            # shared guard (repro.kernels.check_packed_dim): the f32
            # high-res count is exact only to 2**24 — fail at
            # construction, not mid-run in the jit
            check_packed_dim(self.d, where="the packed wire plane")
        self._segments = self._resolve_budget_segments(wp)
        self._resilience = self.engine_cfg.resilience
        if self._resilience is not None:
            if not self.engine_cfg.effective_fused:
                raise ValueError(
                    "the resilience guards trace into the fused round "
                    "step; configure EngineConfig(fused=True) (the "
                    "exact mode's eager sequential replay has no "
                    "guard insertion points)")
            if self._clusters > 1:
                raise ValueError(
                    "resilience guards are not supported with the "
                    "two-level cluster hierarchy (WirePath.clusters > "
                    "1); drop clusters or resilience")
        self.qstate = quantizer.init_batched_state(self.K, self.d)
        self.comp_lat = computation_latency(fl.L, fl.dataset_size_for_comp,
                                            self.K)
        _ENGINE_ORDINAL[0] += 1
        self._obs_name = f"engine{_ENGINE_ORDINAL[0]}[{quantizer.name}]"
        self._user_sharding, self._repl_sharding = self._user_shardings()
        if self._user_sharding is not None:
            # the training set replicated over the mesh; each device
            # gathers only its own users' rows
            self._gather_sharded = jax.jit(
                _gather_minibatches, static_argnums=3,
                in_shardings=(self._repl_sharding, self._repl_sharding,
                              self._user_sharding),
                out_shardings=(self._user_sharding, self._user_sharding))
        if self.engine_cfg.effective_fused:
            self._train_flat = None
            self._fused_step_fn = self._build_fused_step_fn()
            self._fused_step = self._jit_fused_step(self._fused_step_fn)
        else:
            self._train_flat = self._build_train_flat()
            self._fused_step_fn = None
            self._fused_step = None
        # replicate-axis step cache: R -> jitted vmap of the fused step
        self._repl_step_cache = {}
        # async step cache: R (None = unreplicated) -> (train, agg)
        self._async_step_cache = {}
        if self._clusters > 1:
            # two-level hierarchy: per-cluster partial aggregates
            # (cohort scan over the cluster's users — jit retraces per
            # distinct cluster size) + one host-ordered combine and one
            # param-update dispatch
            self._cluster_step = jax.jit(
                _obs.retrace_probe(f"sim.cluster_step/{self._obs_name}")(
                    lambda p, xs, ys, w:
                    self._cohort_accumulate(p, xs, ys, w)))
            self._combine_partials = jax.jit(lambda a, b: a + b)
            self._apply_update = jax.jit(
                lambda p, u: jax.tree_util.tree_map(
                    lambda x, v: x + v, p,
                    unflatten_pytree(u, self.spec)))
            # bits accounting runs the SAME compiled _head_stats graph
            # as the flat fused step, so per-user payload bits stay
            # bitwise-equal across clusters=1 and clusters>1
            self._head_stats_jit = jax.jit(self._head_stats)

    # ------------------------------------------------------------ build
    def _resolve_budget_segments(self, wp: WirePath):
        """Resolve ``WirePath.budget`` against the model's params tree.

        Returns the static segment tuple for a non-uniform budget, or
        None — a uniform/absent budget keeps the pre-existing global
        path, which is the bit-for-bit parity contract (DESIGN.md §13).
        """
        budget = getattr(wp, "effective_budget", None)
        if budget is None:
            return None
        q = self.quantizer
        if q.name != "mixed-resolution":
            raise ValueError(
                "per-layer budgets re-parameterize the mixed-resolution "
                f"scheme per segment; quantizer {q.name!r} has no "
                "(lambda_, b) budget")
        if not self.engine_cfg.effective_fused:
            raise ValueError(
                "per-layer budgets run per-segment quantization inside "
                "the fused step; configure EngineConfig(fused=True) "
                "(the exact mode's eager sequential replay is global-"
                "budget by definition)")
        if self.engine_cfg.async_active:
            raise ValueError(
                "per-layer budgets are not supported in async mode yet; "
                "use LayerBudget.uniform() or sync rounds")
        segments = budget.segments_for(self.params, q.lambda_, q.b)
        if self._plane == "packed":
            for seg in segments:
                if seg.b > 16:
                    raise ValueError(
                        "the wire kernels store magnitude codes in <= 16 "
                        f"bits; budget group {seg.group!r} has b={seg.b}")
        return segments

    def _user_shardings(self):
        """(user-axis, replicated) NamedShardings when an engine mesh
        is configured — the K axis of stacked arrays goes over the
        mesh's data axis so one step runs the users device-parallel."""
        mesh = self.engine_cfg.mesh
        if mesh is None or self._cohort is not None:
            # cohort streaming scans the user axis on one device —
            # __init__ already warned if a mesh was also configured
            return None, None
        from jax.sharding import NamedSharding
        if "data" not in getattr(mesh, "shape", {}):
            warnings.warn("engine mesh has no 'data' axis; user-axis "
                          "sharding disabled", stacklevel=2)
            return None, None
        nd = mesh.shape["data"]
        if self.K % nd != 0:
            warnings.warn(
                f"data axis ({nd}) does not divide K={self.K} users "
                "evenly; user-axis sharding disabled", stacklevel=2)
            return None, None
        return (NamedSharding(mesh, P("data")),
                NamedSharding(mesh, P()))

    def _batched_local(self, params, xs, ys):
        """All stacked users' local AdaGrad runs -> [U, d] deltas
        (U = K vectorized, or one cohort C under streaming).  Traced
        inside the jitted step; batching per EngineConfig."""
        from repro.fl.loop import local_adagrad  # local: avoids cycle

        fl, U = self.fl, xs.shape[0]
        loss = self.model_spec.loss
        if self.engine_cfg.local_batching == "vmap":
            local = jax.vmap(
                lambda x, y: local_adagrad(params, x, y, fl.L, fl.alpha,
                                           loss)
            )(xs, ys)
        else:
            local = jax.lax.map(
                lambda xy: local_adagrad(params, xy[0], xy[1], fl.L,
                                         fl.alpha, loss),
                (xs, ys))
        delta = jax.tree_util.tree_map(lambda w, p: w - p, local, params)
        leaves = jax.tree_util.tree_flatten(delta)[0]
        return jnp.concatenate(
            [jnp.reshape(l, (U, -1)).astype(jnp.float32)
             for l in leaves], axis=1)                        # [U, d]

    # ------------------------------------------- cohort streaming path
    def _head_stats(self, head):
        """Per-user payload bits + aux diagnostics from stacked wire
        headers [U, 8] — the same arithmetic, in the same op order, as
        ``mixed_res_wire_aggregate`` (bitwise-equal bits accounting)."""
        q, d = self.quantizer, self.d
        inf = head[:, H_INF]
        dw_q = head[:, H_DWQ]
        dbar = head[:, H_DBAR]
        s = dbar / d
        bits = d * (q.b * s + 1.0 - s) + 32.0
        bits = jnp.where(inf > 0, bits, float(d) + 32.0)
        aux = {"s": s, "dbar": dbar.astype(jnp.int32), "r": inf - dw_q,
               "dw_q": dw_q, "inf": inf}
        return bits, aux

    def _sharded_wire_aggregate(self, flat, weights):
        """The packed-plane aggregation with the user axis sharded over
        the engine mesh's "data" axis.  Mosaic kernels are not
        partitioned automatically, so inside a shard_map each device
        encodes its own users; the packed planes (never the dense
        deltas) are all-gathered, and every device folds all K users in
        user order.  That is the unsharded fold, so params and payload
        bits equal the one-device run."""
        q, d, wp = self.quantizer, self.d, self.wire_path_spec

        def local(flat_l, w):
            wire = mixed_res_encode(flat_l, q.lambda_, q.b, path=wp)
            wire = jax.tree_util.tree_map(
                lambda a: jax.lax.all_gather(a, "data", tiled=True), wire)
            return mixed_res_wire_reduce(wire, w, q.b, d, path=wp), \
                wire.head

        agg, head = jax.shard_map(
            local, mesh=self.engine_cfg.mesh, in_specs=(P("data"), P()),
            out_specs=(P(), P()), check_vma=False)(flat, weights)
        bits, aux = self._head_stats(head)
        return agg, bits, aux

    def _cohort_accumulate(self, params, xs, ys, weights, faults=None):
        """Stream the stacked users through `lax.scan` in cohorts of
        C = WirePath.cohort_size: each chunk runs local AdaGrad + the
        fused packed encode, and the weighted dequant-reduce folds into
        a carried [d] accumulator (``mixed_res_wire_reduce(acc=...)``)
        — the dense [U, d] gradient matrix never exists at any fan-in.

        The user axis is zero-padded up to a multiple of C; padded
        slots carry weight 0 and so contribute exactly +-0.0 to the
        fold (DESIGN.md §12).  Returns ``(acc [d] f32, head [U, 8])``
        with the padded rows stripped from the headers.

        ``faults`` (resilience path, DESIGN.md §14) adds per-chunk
        inject + detect: bad users' weights zero out inside the fold
        and the carried good-weight total comes back so the CALLER can
        renormalize the whole accumulator GLOBALLY — per-chunk
        renormalization would misweight chunks against each other.
        Resilient returns ``(acc, head, ok [U], wsum, wsum_good)``."""
        q, d, C = self.quantizer, self.d, self._cohort
        wp = self.wire_path_spec
        U = xs.shape[0]
        Gc = -(-U // C)
        pad = Gc * C - U
        resilient = faults is not None
        guards_on = resilient and self._resilience.guards
        wsum = jnp.sum(weights) if resilient else None
        if resilient:
            faults = dict(faults)
        if pad:
            padu = lambda a: jnp.pad(a, [(0, pad)] + [(0, 0)]
                                     * (a.ndim - 1))
            xs, ys, weights = padu(xs), padu(ys), padu(weights)
            if resilient:
                faults = {k: padu(v) for k, v in faults.items()}
        chunk = lambda a: a.reshape((Gc, C) + a.shape[1:])

        def body(acc, args):
            x_c, y_c, w_c = args
            flat = self._batched_local(params, x_c, y_c)  # [C, d]
            wire = mixed_res_encode(flat, q.lambda_, q.b, path=wp)
            acc = mixed_res_wire_reduce(wire, w_c, q.b, d, acc=acc,
                                        path=wp)
            return acc, wire.head

        def body_r(carry, args):
            acc, wg = carry
            x_c, y_c, w_c, f_c = args
            flat = self._batched_local(params, x_c, y_c)  # [C, d]
            flat = _rg.inject_delta_faults(flat, f_c)
            wire = mixed_res_encode(flat, q.lambda_, q.b, path=wp)
            wire = _rg.inject_bitflips(wire, f_c)
            good = ~f_c["drop"]
            if guards_on:
                # head-based O(C) detection: H_INF is a NaN-propagating
                # max|row|, and zeroing a bad row's head makes its
                # planes decode to exactly 0 (guards.sanitize_head) —
                # no second [C, d] isfinite/sanitize pass
                good = good & _rg.head_finite(wire)
                wire = _rg.sanitize_head(wire, good)
            ok = _rg.payload_ok(good, wire,
                                wp.checksum and guards_on)
            # zero bad users out of the fold; the global renorm (one
            # rescale over the full carried sum) happens in the caller
            w_eff = jnp.where(ok, w_c, 0.0)
            acc = mixed_res_wire_reduce(wire, w_eff, q.b, d, acc=acc,
                                        path=wp)
            return (acc, wg + jnp.sum(w_eff)), (wire.head, ok)

        if not resilient:
            acc, heads = jax.lax.scan(
                body, jnp.zeros((d,), jnp.float32),
                (chunk(xs), chunk(ys), chunk(weights)))
            return acc, heads.reshape(Gc * C, -1)[:U]
        (acc, wsum_good), (heads, oks) = jax.lax.scan(
            body_r, (jnp.zeros((d,), jnp.float32), jnp.float32(0.0)),
            (chunk(xs), chunk(ys), chunk(weights),
             {k: chunk(v) for k, v in faults.items()}))
        return (acc, heads.reshape(Gc * C, -1)[:U],
                oks.reshape(-1)[:U], wsum, wsum_good)

    def _build_train_flat(self):
        """One jit dispatch: all K users' local AdaGrad runs + stacked
        delta flattening -> [K, d].  Quantization/aggregation stay
        eager so the dense path replays the sequential loop's per-op
        rounding exactly (see module docstring)."""
        fn = _obs.retrace_probe(f"sim.train_flat/{self._obs_name}")(
            lambda params, xs, ys: self._batched_local(params, xs, ys))
        if self._user_sharding is not None:
            return jax.jit(fn, in_shardings=(
                self._repl_sharding, self._user_sharding,
                self._user_sharding))
        return jax.jit(fn)

    def _build_fused_step_fn(self):
        """The fully fused per-round step (train + batched quantize +
        aggregation + model update), returned UNJITTED so the replicate
        axis can vmap it before compilation."""
        q, spec, K = self.quantizer, self.spec, self.K
        plane, cohort = self._plane, self._cohort
        wp = self.wire_path_spec
        segments = self._segments   # static per-layer budget (or None)

        # per-round straggler/payload stats streamed from INSIDE the
        # compiled step via jax.debug.callback (repro.obs jit tap) —
        # gated at trace time, so without an active session the step
        # compiles to the identical program (tests/test_obs.py)
        def tap(bits, aux, active):
            # same masking as RoundWork.bits_np: absent users carry 0
            masked = bits * active
            stats = {"bits_min": jnp.min(masked),
                     "bits_median": jnp.median(masked),
                     "bits_p95": jnp.percentile(masked, 95.0),
                     "bits_mean": jnp.mean(masked),
                     "active_frac": jnp.mean(active)}
            if "s" in aux:
                # high-res fraction averaged over ACTIVE users, as in
                # RoundWork.mean_s
                stats["mean_s"] = (jnp.sum(aux["s"] * active)
                                   / jnp.maximum(jnp.sum(active), 1.0))
            _obs.jit_tap("engine.jit_round", stats)

        def step(params, qstate, xs, ys, weights, active):
            if plane == "packed" and cohort is not None:
                # streaming cohorts: the scan body trains + encodes C
                # users at a time and folds their packed planes into
                # the carried [d] accumulator — no [K, d] buffer
                acc, head = self._cohort_accumulate(params, xs, ys,
                                                    weights)
                bits, aux = self._head_stats(head)
                params = jax.tree_util.tree_map(
                    lambda p, u: p + u, params,
                    unflatten_pytree(acc, spec))
                tap(bits, aux, active)
                return params, qstate, bits, aux
            flat = self._batched_local(params, xs, ys)
            if plane == "packed":
                # fully fused quantize-to-wire: reductions, packed
                # planes and the weighted dequant-reduce all happen in
                # the mixed-res kernel suite; no dense recon, and no
                # quantizer state (mixed-resolution is stateless).
                # Under a per-layer budget the encode/reduce runs once
                # per segment with that group's (lambda_, b); bits is
                # the exact per-segment sum (DESIGN.md §13)
                if segments is not None:
                    agg, bits, aux = segmented_wire_aggregate(
                        flat, weights, segments, path=wp)
                elif self._user_sharding is not None:
                    agg, bits, aux = self._sharded_wire_aggregate(
                        flat, weights)
                else:
                    agg, bits, aux = _wire_aggregate(flat, weights,
                                                     q.lambda_, q.b,
                                                     path=wp)
                params = jax.tree_util.tree_map(
                    lambda p, u: p + u, params,
                    unflatten_pytree(agg, spec))
                tap(bits, aux, active)
                return params, qstate, bits, aux
            if segments is not None:
                # dense plane, per-layer budget: per-segment stateless
                # mixed-resolution quantize + the einsum aggregation
                recon, bits, aux = segmented_quantize(flat, segments)
                agg = jnp.einsum("k,kd->d", weights, recon)
                params = jax.tree_util.tree_map(
                    lambda p, u: p + u, params,
                    unflatten_pytree(agg, spec))
                tap(bits, aux, active)
                return params, qstate, bits, aux
            res, new_qstate = q.batched(flat, qstate)
            if new_qstate is not None:
                # absent users did not transmit: freeze their state
                new_qstate = jax.tree_util.tree_map(
                    lambda n, o: jnp.where(
                        jnp.reshape(active, (K,) + (1,) * (n.ndim - 1))
                        > 0, n, o),
                    new_qstate, qstate)
            if plane == "signplane":
                agg = _signplane_aggregate(flat, res.recon,
                                           res.aux["dw_q"], weights)
            else:
                agg = jnp.einsum("k,kd->d", weights, res.recon)
            params = jax.tree_util.tree_map(
                lambda p, u: p + u, params, unflatten_pytree(agg, spec))
            tap(res.bits, res.aux, active)
            return params, new_qstate, res.bits, res.aux

        if self._resilience is None:
            return step

        # ---- resilience variant (DESIGN.md §14): same arithmetic with
        # inject/detect/quarantine threaded through.  Faults arrive as
        # plain arrays (host-drawn, repro.resilience.faults) so nothing
        # here branches on them; every guard is where-gated, keeping a
        # no-fault round bit-for-bit with the pristine step above
        # (tests/test_resilience.py parity battery).
        guards_on = self._resilience.guards
        d = self.d

        def finish(params, qstate, agg, ok, bits, aux, active):
            """Shared epilogue: quarantine accounting, the final finite
            guard on the aggregated update (freeze the global model for
            the round when everything failed), param update."""
            new = jax.tree_util.tree_map(
                lambda p, u: p + u, params, unflatten_pytree(agg, spec))
            if guards_on:
                okall = _rg.update_ok(agg) & jnp.any(ok)
                params = jax.tree_util.tree_map(
                    lambda n, o: jnp.where(okall, n, o), new, params)
            else:
                okall = jnp.asarray(True)
                params = new
            aux = dict(aux)
            aux["quarantined"] = _rg.quarantined_count(ok, active)
            aux["update_ok"] = okall
            tap(bits, aux, active)
            return params, qstate, bits, aux

        def step_r(params, qstate, xs, ys, weights, active, faults):
            if plane == "packed" and cohort is not None:
                acc, head, ok, wsum, wsum_good = self._cohort_accumulate(
                    params, xs, ys, weights, faults=faults)
                bits, aux = self._head_stats(head)
                # GLOBAL renormalization across all chunks: one rescale
                # of the carried sum, gated so the no-fault fold keeps
                # its exact bits
                any_bad = ~jnp.all(ok)
                scale = wsum / jnp.where(wsum_good > 0, wsum_good, 1.0)
                acc = jnp.where(any_bad, acc * scale, acc)
                return finish(params, qstate, acc, ok, bits, aux,
                              active)
            flat = self._batched_local(params, xs, ys)
            flat = _rg.inject_delta_faults(flat, faults)
            good = ~faults["drop"]
            if plane == "packed" and segments is None:
                # decomposed _wire_aggregate — identical op sequence,
                # with the in-transit bitflip + checksum verify between
                # encode and decode.  Detection reads the encode's own
                # header (head_finite/sanitize_head): O(K) on the
                # 8-float heads instead of an O(K d) isfinite pass +
                # a second [K, d] sanitized buffer
                wire = mixed_res_encode(flat, q.lambda_, q.b, path=wp)
                wire = _rg.inject_bitflips(wire, faults)
                if guards_on:
                    good = good & _rg.head_finite(wire)
                    wire = _rg.sanitize_head(wire, good)
                ok = _rg.payload_ok(good, wire,
                                    wp.checksum and guards_on)
                w_eff, _ = _rg.quarantine_weights(weights, ok)
                agg = mixed_res_wire_reduce(wire, w_eff, q.b, d,
                                            path=wp)
                bits, aux = self._head_stats(wire.head)
                return finish(params, qstate, agg, ok, bits, aux,
                              active)
            if guards_on:
                # dense/segmented recons: NaN rides the payload itself
                # (NaN * 0 = NaN), so bad rows must be zeroed in the
                # delta matrix before quantization
                good = good & _rg.finite_rows(flat)
                flat = _rg.sanitize_rows(flat, good)
            if plane == "packed":
                # per-layer budget: delta-level faults + quarantine
                # only (bitflips/checksums are per-segment wires —
                # not modeled; the flip draw is ignored here)
                ok = good
                w_eff, _ = _rg.quarantine_weights(weights, ok)
                agg, bits, aux = segmented_wire_aggregate(
                    flat, w_eff, segments, path=wp)
                return finish(params, qstate, agg, ok, bits, aux,
                              active)
            ok = good
            w_eff, _ = _rg.quarantine_weights(weights, ok)
            if segments is not None:
                recon, bits, aux = segmented_quantize(flat, segments)
                agg = jnp.einsum("k,kd->d", w_eff, recon)
                return finish(params, qstate, agg, ok, bits, aux,
                              active)
            res, new_qstate = q.batched(flat, qstate)
            if new_qstate is not None:
                # quarantined users did not (effectively) transmit:
                # freeze their state along with the absent users'
                commit = jnp.where(ok, active, 0.0)
                new_qstate = jax.tree_util.tree_map(
                    lambda n, o: jnp.where(
                        jnp.reshape(commit, (K,) + (1,) * (n.ndim - 1))
                        > 0, n, o),
                    new_qstate, qstate)
                qstate = new_qstate
            if plane == "signplane":
                agg = _signplane_aggregate(flat, res.recon,
                                           res.aux["dw_q"], w_eff)
            else:
                agg = jnp.einsum("k,kd->d", w_eff, res.recon)
            return finish(params, qstate, agg, ok, res.bits, res.aux,
                          active)

        return step_r

    def _jit_fused_step(self, step):
        # params and quantizer state are round-to-round carries: donate
        # them so XLA reuses their buffers instead of copying every
        # round (start_run hands the step private copies, so the
        # engine's own init arrays survive repeated runs)
        step = _obs.retrace_probe(
            f"sim.fused_step/{self._obs_name}")(step)
        if self._user_sharding is not None:
            us, rs = self._user_sharding, self._repl_sharding
            # params replicated; every stacked [K, ...] arg (quantizer
            # state, minibatches, weights, activity mask — and the
            # resilience fault-mask dict, when threaded) user-sharded
            shardings = (rs, us, us, us, us, us)
            if self._resilience is not None:
                shardings = shardings + (us,)
            return jax.jit(step, in_shardings=shardings,
                           donate_argnums=(0, 1))
        return jax.jit(step, donate_argnums=(0, 1))

    def _replicated_step(self, R: int):
        """The per-round step over a leading replicate axis R — ONE
        jitted dispatch for all R trajectories.

        R == 1 routes through the SAME compiled function as the
        unreplicated driver (``self._fused_step`` on squeezed arrays):
        a vmap over a singleton axis recompiles the graph with batched
        lowerings and is only roundoff-equal, while the squeeze keeps
        the R=1 replicated path bit-for-bit with today's driver
        (tests/test_mc_replicates.py).
        """
        if R not in self._repl_step_cache:
            if R == 1:
                fused = self._fused_step

                def step1(params, qstate, xs, ys, weights, active,
                          *rest):
                    sq = lambda tr: jax.tree_util.tree_map(
                        lambda x: x[0], tr)
                    p, q, bits, aux = fused(sq(params), sq(qstate),
                                            xs[0], ys[0], weights[0],
                                            active[0],
                                            *[sq(r) for r in rest])
                    ex = lambda tr: jax.tree_util.tree_map(
                        lambda x: x[None], tr)
                    return ex(p), ex(q), bits[None], ex(aux)

                self._repl_step_cache[R] = step1
            else:
                if self._user_sharding is not None:
                    warnings.warn(
                        "EngineConfig.mesh user-axis sharding is not "
                        "supported in replicated mode (R > 1); running "
                        "unsharded", stacklevel=2)
                fn = self._fused_step_fn
                mode = self.engine_cfg.replicate_batching
                if mode == "auto":
                    mode = "vmap" if jax.default_backend() in (
                        "tpu", "gpu") else "map"
                if self._plane in ("signplane", "packed"):
                    # the Pallas wire-format kernels expect their
                    # unbatched [G*W, 128] windows — never vmap them
                    mode = "map"
                # the stacked params/qstate carries are donated round
                # to round, same as the unreplicated fused step
                probe = _obs.retrace_probe(
                    f"sim.replicated_step/{self._obs_name}/R{R}")
                if mode == "map":
                    # on-device loop INSIDE the one jitted dispatch:
                    # per-replicate convs keep the fast unbatched CPU
                    # lowering (see EngineConfig.replicate_batching).
                    # *args: the resilient step carries a trailing
                    # fault-mask dict after the six standard operands
                    self._repl_step_cache[R] = jax.jit(
                        probe(lambda *args: jax.lax.map(
                            lambda a: fn(*a), args)),
                        donate_argnums=(0, 1))
                else:
                    self._repl_step_cache[R] = jax.jit(
                        probe(jax.vmap(fn)), donate_argnums=(0, 1))
        return self._repl_step_cache[R]

    # ------------------------------------------------- async machinery
    # The async round splits the fused step in two: a train+quantize
    # dispatch producing the fresh device payloads (no aggregation, no
    # param update) and, after the host event clock has decided who
    # arrived, an aggregate+buffer-shuffle dispatch.  Still a constant
    # number of jitted calls per round regardless of K and R
    # (tests/test_async_engine.py counts them).
    def _build_async_train_fn(self):
        """Unjitted (params, qstate, xs, ys, commit) ->
        (payload, new_qstate, bits, aux).  ``commit`` is the
        fresh-uploader mask: only committing users' quantizer state
        advances (busy/absent users did not transmit)."""
        q, K, d = self.quantizer, self.K, self.d
        plane, wp = self._plane, self.wire_path_spec

        def tap(bits, aux, commit):
            masked = bits * commit
            stats = {"bits_min": jnp.min(masked),
                     "bits_median": jnp.median(masked),
                     "bits_p95": jnp.percentile(masked, 95.0),
                     "bits_mean": jnp.mean(masked),
                     "active_frac": jnp.mean(commit)}
            if "s" in aux:
                stats["mean_s"] = (jnp.sum(aux["s"] * commit)
                                   / jnp.maximum(jnp.sum(commit), 1.0))
            _obs.jit_tap("engine.jit_round", stats)

        def train(params, qstate, xs, ys, commit):
            flat = self._batched_local(params, xs, ys)
            if plane == "packed":
                wire = mixed_res_encode(flat, q.lambda_, q.b, path=wp)
                bits, aux = self._head_stats(wire.head)
                tap(bits, aux, commit)
                return wire, qstate, bits, aux
            res, new_qstate = q.batched(flat, qstate)
            if new_qstate is not None:
                new_qstate = jax.tree_util.tree_map(
                    lambda n, o: jnp.where(
                        jnp.reshape(commit, (K,) + (1,) * (n.ndim - 1))
                        > 0, n, o),
                    new_qstate, qstate)
            tap(res.bits, res.aux, commit)
            return res.recon, new_qstate, res.bits, res.aux

        if self._resilience is None:
            return train

        # resilience variant (DESIGN.md §14): a quarantined payload is
        # equivalent to an upload that never started — the host folds
        # aux["payload_ok"] into the fresh mask, so the event clock
        # carries no in-flight record and the buffer never sees it.
        # Packed payloads are neutralized by zeroing the wire header
        # (O(K)); dense recons need the bad rows zeroed BEFORE
        # quantization, since NaN * 0 = NaN would otherwise poison the
        # aggregate through a weight-0 slot.
        guards_on = self._resilience.guards

        def train_r(params, qstate, xs, ys, commit, faults):
            flat = self._batched_local(params, xs, ys)
            flat = _rg.inject_delta_faults(flat, faults)
            good = ~faults["drop"]
            if plane == "packed":
                # head-based detection (see step_r): a quarantined
                # wire's zeroed head decodes to exactly 0 even if it
                # lingers in the staleness buffer
                wire = mixed_res_encode(flat, q.lambda_, q.b, path=wp)
                wire = _rg.inject_bitflips(wire, faults)
                if guards_on:
                    good = good & _rg.head_finite(wire)
                    wire = _rg.sanitize_head(wire, good)
                ok = _rg.payload_ok(good, wire,
                                    wp.checksum and guards_on)
                bits, aux = self._head_stats(wire.head)
                aux = dict(aux)
                aux["payload_ok"] = ok
                tap(bits, aux, commit)
                return wire, qstate, bits, aux
            if guards_on:
                good = good & _rg.finite_rows(flat)
                flat = _rg.sanitize_rows(flat, good)
            res, new_qstate = q.batched(flat, qstate)
            if new_qstate is not None:
                commit_eff = jnp.where(good, commit, 0.0)
                new_qstate = jax.tree_util.tree_map(
                    lambda n, o: jnp.where(
                        jnp.reshape(commit_eff,
                                    (K,) + (1,) * (n.ndim - 1))
                        > 0, n, o),
                    new_qstate, qstate)
            aux = dict(res.aux)
            aux["payload_ok"] = good
            tap(res.bits, aux, commit)
            return res.recon, new_qstate, res.bits, aux

        return train_r

    def _build_async_agg_fn(self):
        """Unjitted (params, fresh, buf, w_fresh, w_buf, move, keep) ->
        (params, new_buf): staleness-weighted aggregation over the
        arrived fresh + buffered payloads (all-zero weights mean no
        arrivals — params pass through unchanged) and the buffer
        shuffle (missed fresh payloads move in, retained misses stay,
        everything else zeroes out)."""
        q, spec, K, d = self.quantizer, self.spec, self.K, self.d
        plane, wp = self._plane, self.wire_path_spec

        def agg(params, fresh, buf, w_fresh, w_buf, move, keep):
            if plane == "packed":
                stacked = jax.tree_util.tree_map(
                    lambda f, bu: jnp.concatenate([f, bu], axis=0),
                    fresh, buf)
                w = jnp.concatenate([w_fresh, w_buf], axis=0)
                upd = mixed_res_wire_reduce(stacked, w, q.b, d, path=wp)
            else:
                upd = (jnp.einsum("k,kd->d", w_fresh, fresh)
                       + jnp.einsum("k,kd->d", w_buf, buf))
            params = jax.tree_util.tree_map(
                lambda p, u: p + u, params, unflatten_pytree(upd, spec))

            def shuffle(f, bu):
                m = jnp.reshape(move, (K,) + (1,) * (f.ndim - 1)) > 0
                kp = jnp.reshape(keep, (K,) + (1,) * (f.ndim - 1)) > 0
                return jnp.where(m, f, jnp.where(kp, bu,
                                                 jnp.zeros_like(bu)))

            new_buf = jax.tree_util.tree_map(shuffle, fresh, buf)
            _obs.jit_tap("engine.async_agg",
                         {"w_fresh_sum": jnp.sum(w_fresh),
                          "w_buf_sum": jnp.sum(w_buf),
                          "buf_occupancy": jnp.mean(move + keep)})
            return params, new_buf

        return agg

    def _async_steps(self, R: Optional[int] = None) -> Tuple:
        """(train, agg) jitted async dispatches for replicate count R
        (None = unreplicated).  R=1 routes through the SAME compiled
        functions as the unreplicated path via squeeze/expand — the
        same idiom (and for the same bit-for-bit reason) as
        ``_replicated_step``."""
        if R not in self._async_step_cache:
            train_fn = self._build_async_train_fn()
            agg_fn = self._build_async_agg_fn()
            probe_t = _obs.retrace_probe(
                f"sim.async_train/{self._obs_name}"
                + ("" if R is None else f"/R{R}"))
            probe_a = _obs.retrace_probe(
                f"sim.async_agg/{self._obs_name}"
                + ("" if R is None else f"/R{R}"))
            if R is None:
                # params survive the train dispatch (the agg dispatch
                # still needs them), so only qstate is donated there;
                # the agg dispatch donates its params + buffer carries
                # (the fresh payload is not donated: only one
                # buffer-shaped output exists for XLA to alias)
                self._async_step_cache[R] = (
                    jax.jit(probe_t(train_fn), donate_argnums=(1,)),
                    jax.jit(probe_a(agg_fn), donate_argnums=(0, 2)))
            elif R == 1:
                train1, agg1 = self._async_steps(None)

                def sq(tr):
                    return jax.tree_util.tree_map(lambda x: x[0], tr)

                def ex(tr):
                    return jax.tree_util.tree_map(lambda x: x[None], tr)

                def train_r1(params, qstate, xs, ys, commit, *rest):
                    pay, qs, bits, aux = train1(sq(params), sq(qstate),
                                                xs[0], ys[0], commit[0],
                                                *[sq(r) for r in rest])
                    return ex(pay), ex(qs), bits[None], ex(aux)

                def agg_r1(params, fresh, buf, w_fresh, w_buf, move,
                           keep):
                    p, nb = agg1(sq(params), sq(fresh), sq(buf),
                                 w_fresh[0], w_buf[0], move[0], keep[0])
                    return ex(p), ex(nb)

                self._async_step_cache[R] = (train_r1, agg_r1)
            else:
                mode = self.engine_cfg.replicate_batching
                if mode == "auto":
                    mode = "vmap" if jax.default_backend() in (
                        "tpu", "gpu") else "map"
                if self._plane == "packed":
                    mode = "map"    # Pallas kernels: unbatched windows
                if mode == "map":
                    batch = lambda fn: (lambda *args: jax.lax.map(
                        lambda a: fn(*a), args))
                else:
                    batch = jax.vmap
                self._async_step_cache[R] = (
                    jax.jit(probe_t(batch(train_fn)),
                            donate_argnums=(1,)),
                    jax.jit(probe_a(batch(agg_fn)),
                            donate_argnums=(0, 2)))
        return self._async_step_cache[R]

    def _init_async_clock(self, R: Optional[int] = None) -> AsyncClock:
        """Empty bounded-staleness buffer: host masks all-clear, device
        payload slots all-zero (a zero slot with weight zero contributes
        exactly nothing to the aggregate)."""
        B = 1 if R is None else R
        K, d = self.K, self.d
        if self._plane == "packed":
            shapes = jax.eval_shape(
                lambda z: mixed_res_encode(z, self.quantizer.lambda_,
                                           self.quantizer.b),
                jax.ShapeDtypeStruct((K, d), jnp.float32))
            zero = lambda sd: jnp.zeros(sd.shape if R is None
                                        else (R,) + sd.shape, sd.dtype)
            buffer = jax.tree_util.tree_map(zero, shapes)
        else:
            buffer = jnp.zeros((K, d) if R is None else (R, K, d),
                               jnp.float32)
        return AsyncClock(
            in_flight=np.zeros((B, K), bool),
            remaining_s=np.zeros((B, K)),
            staleness=np.zeros((B, K), np.int64),
            buffer=buffer)

    # ----------------------------------------------------------- rounds
    def _dense_round(self, params, qstate, xs, ys, weights, active_np):
        """Eager quantize + user-ordered weighted aggregation: replays
        the sequential loop's arithmetic op for op."""
        flat = self._train_flat(params, xs, ys)
        res, new_qstate = self.quantizer.batched(flat, qstate)
        if new_qstate is not None:
            if self.engine_cfg.participation >= 1.0:
                qstate = new_qstate
            else:
                act = jnp.asarray(active_np, jnp.float32)
                qstate = jax.tree_util.tree_map(
                    lambda n, o: jnp.where(
                        jnp.reshape(act, (self.K,) + (1,) * (n.ndim - 1))
                        > 0, n, o),
                    new_qstate, qstate)
        # same left-to-right summation as the sequential Python sum
        agg = None
        for j in range(self.K):
            term = res.recon[j] * weights[j]
            agg = term if agg is None else agg + term
        params = jax.tree_util.tree_map(
            lambda p, u: p + u, params, unflatten_pytree(agg, self.spec))
        return params, qstate, res.bits, res.aux

    # ------------------------------------------------------------- run
    def _draw_faults(self, t: int, R: Optional[int] = None):
        """The round's fault masks as device arrays ([K], or stacked
        [R, K]) — None without a resilience config (the pristine step
        signatures take no faults argument)."""
        if self._resilience is None:
            return None
        plan = self._resilience.faults
        if R is None:
            f = plan.draw(t, self.K)
        else:
            per_r = [plan.draw(t, self.K, replicate=r) for r in range(R)]
            f = {k: np.stack([p[k] for p in per_r]) for k in per_r[0]}
        return {k: jnp.asarray(v) for k, v in f.items()}

    def _draw_active(self, part_rng: np.random.Generator) -> np.ndarray:
        p = self.engine_cfg.participation
        if p >= 1.0:
            return np.ones(self.K)
        mask = part_rng.random(self.K) < p
        if not mask.any():                      # never an empty round
            mask[int(part_rng.integers(self.K))] = True
        return mask.astype(np.float64)

    def _round_weights(self, active: np.ndarray) -> np.ndarray:
        if self.engine_cfg.participation >= 1.0:
            return self.rho                     # exactly the paper's rho
        w = self.rho * active
        return w / w.sum()

    # ----------------------------------------------- round-stepping API
    # run() composes these four stages; repro.sim.phy_driver drives the
    # same stages for a whole grid of cells, replacing the per-cell
    # host solve of stage 3 with one batched device solve per round.
    def start_run(self) -> RunState:
        fl = self.fl
        # private copies: the fused step donates its params/qstate
        # inputs, and the engine's init arrays must survive re-runs
        copy = lambda tr: jax.tree_util.tree_map(
            lambda x: jnp.asarray(x).copy(), tr)
        return RunState(
            params=copy(self.params), qstate=copy(self.qstate),
            chan=self.chan,
            rng=np.random.default_rng(fl.seed),   # sequential-loop stream
            part_rng=np.random.default_rng((fl.seed, 0x5EED)),
            test_x=jnp.asarray(self.test.x),
            test_y=jnp.asarray(self.test.y), logs=[],
            async_clock=self._init_async_clock()
            if self.engine_cfg.async_active else None)

    def train_round(self, state: RunState, t: int) -> RoundWork:
        """Stage 1-2: channel redraw, minibatch draw, the jitted local
        training + quantization + aggregation step.  Updates ``state``
        in place and returns the payload the power stage needs."""
        fl, ecfg = self.fl, self.engine_cfg
        if (ecfg.redraw_channel_every > 0 and state.chan is not None
                and t > 1
                and (t - 1) % ecfg.redraw_channel_every == 0):
            state.chan = make_channel(state.chan.cfg,
                                      seed=ecfg.channel_seed + t)
        with _obs.scope("engine.draw"):
            # same nested draw order as the sequential loop
            sel = np.stack([
                np.stack([state.rng.choice(shard, self.take,
                                           replace=False)
                          for _ in range(fl.L)])
                for shard in self.shards])           # [K, L, b]
            active = self._draw_active(state.part_rng)
        if self._clusters > 1 and not ecfg.async_active:
            # two-level hierarchy: only one cluster's minibatches are
            # gathered (and resident) at a time
            return self._clustered_round(state, t, sel, active)
        faults = self._draw_faults(t)
        extra = () if faults is None else (faults,)
        if ecfg.async_active:
            # async: busy users (mid-upload) keep transmitting their
            # old payload — only participating, non-busy users start a
            # FRESH upload this round; the aggregation happens later in
            # complete_round_async, once arrivals are known
            clock = state.async_clock
            fresh = active * (~clock.in_flight[0]).astype(np.float64)
            xs, ys, fresh_dev = self._minibatches(sel, fresh)
            train_step, _ = self._async_steps(None)
            with _obs.scope("engine.dispatch"):
                clock.payload, state.qstate, bits, aux = train_step(
                    state.params, state.qstate, xs, ys, fresh_dev,
                    *extra)
            bits_np, s_np = self._fetch(bits, aux)
            clock.uploads_started += int(fresh.sum())
            quarantined = 0
            if faults is not None:
                # quarantined payload == upload that never happened:
                # fold the verdict into the fresh mask BEFORE the event
                # clock sees it
                ok_np = np.asarray(aux["payload_ok"], bool)
                quarantined = int(np.sum(fresh.astype(bool) & ~ok_np))
                fresh = fresh * ok_np
            bits_np = bits_np * fresh
            fb = fresh.astype(bool)
            mean_s = float(np.mean(s_np[fb])) if fb.any() else 0.0
            return RoundWork(t=t, bits_np=bits_np, active=fresh,
                             mean_s=mean_s, participating=active,
                             quarantined=quarantined)
        weights = self._round_weights(active)
        if not ecfg.effective_fused:
            xs, ys = self._minibatches(sel, user_sharded=True)
            with _obs.scope("engine.dispatch"):
                state.params, state.qstate, bits, aux = \
                    self._dense_round(state.params, state.qstate, xs, ys,
                                      weights, active)
        else:
            xs, ys, w_dev, a_dev = self._minibatches(
                sel, weights, active, user_sharded=True)
            with _obs.scope("engine.dispatch"):
                state.params, state.qstate, bits, aux = self._fused_step(
                    state.params, state.qstate, xs, ys, w_dev, a_dev,
                    *extra)
        bits_np, s_np = self._fetch(bits, aux)
        quarantined = int(aux["quarantined"]) if faults is not None \
            else 0
        bits_np = bits_np * active
        mean_s = float(np.mean(s_np[active.astype(bool)]))
        return RoundWork(t=t, bits_np=bits_np, active=active,
                         mean_s=mean_s, quarantined=quarantined)

    def _minibatches(self, sel: np.ndarray, *vectors: np.ndarray,
                     user_sharded: bool = False):
        """The minibatches that ``sel`` indexes, gathered on the device
        from the resident training set (``engine.gather``: the indices'
        transfer as int32 and the gather's dispatch), and the round's
        per-user ``vectors`` moved to the device as float32
        (``engine.h2d``).  ``engine.h2d_bytes`` counts what crosses to
        the device: the indices and the vectors, not the images.

        ``user_sharded``: the caller's step takes the users in the
        engine's user sharding (the sync step, the exact path).  Under
        a mesh the set is then replicated over it and the minibatches
        come out user-sharded, each device gathering its own users'
        rows; otherwise they sit uncommitted on the default device."""
        sel = np.asarray(sel, np.int32)
        _obs.counter("engine.h2d_bytes",
                     sel.nbytes + 4 * sum(np.size(v) for v in vectors))
        if user_sharded and self._user_sharding is not None:
            gather, placement = self._gather_sharded, self._repl_sharding
        else:
            gather, placement = _gather_default, None
        with _obs.scope("engine.gather"):
            xs, ys = gather(*_resident_set(self.dataset, placement), sel,
                            self.dataset.x.shape[1:])
        with _obs.scope("engine.h2d"):
            return (xs, ys,
                    *(jnp.asarray(v, jnp.float32) for v in vectors))

    def _fetch(self, bits, aux) -> Tuple[np.ndarray, np.ndarray]:
        """The round's payload bits and slot shares on the host
        (``engine.fetch``), which waits for the step that makes them."""
        with _obs.scope("engine.fetch"):
            bits_np = np.asarray(bits, np.float64)
            s_np = np.asarray(aux["s"], np.float64) if "s" in aux \
                else np.ones(np.shape(bits_np))
        return bits_np, s_np

    def _clustered_round(self, state: RunState, t: int, sel: np.ndarray,
                         active: np.ndarray) -> RoundWork:
        """Two-level hierarchy (WirePath.clusters > 1, DESIGN.md §12):
        the K users are split host-side into contiguous AP-cluster
        groups; each group's minibatches are gathered alone and its
        cohort scan produces a partial [d] aggregate on device.  The
        partials combine in fixed cluster order (one tiny dispatch per
        hop) before a single param-update dispatch — neither a [K, d]
        buffer nor the full K-user minibatch stack is ever resident.

        Combining per-cluster partials reassociates the user fold, so
        this path matches ``clusters=1`` only to float32 roundoff
        (DESIGN.md §12), never bit-for-bit."""
        weights = self._round_weights(active)
        groups = np.array_split(np.arange(self.K), self._clusters)
        total, heads = None, []
        for g in groups:
            xs, ys, w_dev = self._minibatches(sel[g], weights[g])
            with _obs.scope("engine.dispatch"):
                part, head = self._cluster_step(state.params, xs, ys,
                                                w_dev)
                total = part if total is None \
                    else self._combine_partials(total, part)
            heads.append(head)
        with _obs.scope("engine.dispatch"):
            state.params = self._apply_update(state.params, total)
            # bits from the SAME jitted _head_stats graph the flat
            # cohort step runs — payload accounting is bitwise
            # cluster-invariant
            bits, aux = self._head_stats_jit(
                jnp.concatenate(heads, axis=0))
        bits_np, s = self._fetch(bits, aux)
        bits_np = bits_np * active
        mean_s = float(np.mean(s[active.astype(bool)]))
        return RoundWork(t=t, bits_np=bits_np, active=active,
                         mean_s=mean_s)

    # ------------------------------------------- replicated round API
    # The Monte-Carlo replicate axis (DESIGN.md section 8): R
    # independent trajectories of this engine's problem advance in ONE
    # jitted dispatch per round.  The replicated grid driver
    # (repro.sim.phy_driver) owns the per-(cell, replicate) latency
    # accounting; these methods own training state and RNG-stream
    # folding.
    def _repl_chan_seed(self, r: int, t: int) -> int:
        return (self.engine_cfg.channel_seed
                + r * _REPL_CHANNEL_SEED_STRIDE + t)

    def start_replicated_run(self, R: int) -> ReplicatedRunState:
        if not self.engine_cfg.effective_fused:
            raise ValueError(
                "replicated mode vmaps the fused per-round step; "
                "configure EngineConfig(fused=True)")
        if self._clusters > 1:
            raise ValueError(
                "the two-level cluster hierarchy drives its per-cluster "
                "dispatches from the host; replicated mode is not "
                "supported with WirePath.clusters > 1")
        if R < 1:
            raise ValueError(f"need at least one replicate, got {R}")
        fl = self.fl
        stack = lambda tr: jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x[None], (R,) + x.shape), tr)
        chans: List[Optional[ChannelRealization]] = [self.chan]
        for r in range(1, R):
            chans.append(None if self.chan is None else make_channel(
                self.chan.cfg, seed=self._repl_chan_seed(r, 0)))
        return ReplicatedRunState(
            params=stack(self.params), qstate=stack(self.qstate),
            chans=chans,
            # replicate 0 keeps the unreplicated streams bit-for-bit
            rngs=[np.random.default_rng(fl.seed) if r == 0 else
                  np.random.default_rng((fl.seed, _REPL_TAG, r))
                  for r in range(R)],
            part_rngs=[np.random.default_rng((fl.seed, 0x5EED)) if r == 0
                       else np.random.default_rng(
                           (fl.seed, 0x5EED, _REPL_TAG, r))
                       for r in range(R)],
            test_x=jnp.asarray(self.test.x),
            test_y=jnp.asarray(self.test.y),
            async_clock=self._init_async_clock(R)
            if self.engine_cfg.async_active else None)

    def train_round_replicated(self, state: ReplicatedRunState, t: int
                               ) -> ReplicatedRoundWork:
        """All R replicates' (channel redraw, minibatch draw, jitted
        train + quantize + aggregate) for round t — one device
        dispatch.  Updates ``state`` in place."""
        fl, ecfg, R = self.fl, self.engine_cfg, state.R
        if (ecfg.redraw_channel_every > 0 and t > 1
                and (t - 1) % ecfg.redraw_channel_every == 0):
            for r in range(R):
                if state.chans[r] is not None:
                    state.chans[r] = make_channel(
                        state.chans[r].cfg,
                        seed=self._repl_chan_seed(r, t))
        # per replicate, the same nested draw order as train_round
        sel = np.stack([
            np.stack([
                np.stack([rng.choice(shard, self.take, replace=False)
                          for _ in range(fl.L)])
                for shard in self.shards])
            for rng in state.rngs])                  # [R, K, L, b]
        xs, ys = self._minibatches(sel)
        active = np.stack([self._draw_active(prng)
                           for prng in state.part_rngs])      # [R, K]
        faults = self._draw_faults(t, R)
        if ecfg.async_active:
            clock = state.async_clock
            fresh = active * (~clock.in_flight).astype(np.float64)
            train_step, _ = self._async_steps(R)
            clock.payload, state.qstate, bits, aux = train_step(
                state.params, state.qstate, xs, ys,
                jnp.asarray(fresh, jnp.float32),
                *(() if faults is None else (faults,)))
            clock.uploads_started += int(fresh.sum())
            quarantined = None
            if faults is not None:
                ok_np = np.asarray(aux["payload_ok"], bool)
                quarantined = np.sum(fresh.astype(bool) & ~ok_np,
                                     axis=-1).astype(np.int64)
                fresh = fresh * ok_np
            state.rounds_done = t
            bits_np = np.asarray(bits, np.float64) * fresh
            s_np = np.asarray(aux["s"], np.float64) if "s" in aux \
                else np.ones((R, self.K))
            mean_s = np.array([
                float(np.mean(s_np[r][fresh[r].astype(bool)]))
                if fresh[r].any() else 0.0 for r in range(R)])
            return ReplicatedRoundWork(t=t, bits_np=bits_np,
                                       active=fresh, mean_s=mean_s,
                                       participating=active,
                                       quarantined=quarantined)
        weights = np.stack([self._round_weights(a) for a in active])
        step = self._replicated_step(R)
        state.params, state.qstate, bits, aux = step(
            state.params, state.qstate, xs, ys,
            jnp.asarray(weights, jnp.float32),
            jnp.asarray(active, jnp.float32),
            *(() if faults is None else (faults,)))
        quarantined = None if faults is None else \
            np.asarray(aux["quarantined"], np.int64)
        state.rounds_done = t
        bits_np = np.asarray(bits, np.float64) * active
        s_np = np.asarray(aux["s"], np.float64) if "s" in aux \
            else np.ones((R, self.K))
        mean_s = np.array([float(np.mean(s_np[r][active[r].astype(bool)]))
                           for r in range(R)])
        return ReplicatedRoundWork(t=t, bits_np=bits_np, active=active,
                                   mean_s=mean_s,
                                   quarantined=quarantined)

    def complete_round_replicated_async(
            self, state: ReplicatedRunState, work: ReplicatedRoundWork,
            per_user_s: np.ndarray) -> AsyncRoundInfo:
        """Replicated async stage 3.5: R event clocks advance host-side
        and ONE jitted aggregate dispatch updates all R replicates'
        params + buffers.  ``per_user_s``: [R, K] solve latencies."""
        R = state.R
        clock = state.async_clock
        step, info = self._advance_clock(
            clock, work.active, work.participating,
            np.asarray(per_user_s, np.float64))
        _, agg_step = self._async_steps(R)
        f32 = lambda a: jnp.asarray(a, jnp.float32)
        state.params, clock.buffer = agg_step(
            state.params, clock.payload, clock.buffer,
            f32(step.w_fresh), f32(step.w_buf),
            f32(step.move), f32(step.keep))
        clock.payload = None
        self._record_async(work.t, info)
        return info

    def replicate_params(self, state: ReplicatedRunState, r: int):
        """Replicate r's current param pytree (device view)."""
        return jax.tree_util.tree_map(lambda x: x[r], state.params)

    # Both drivers (finish_round below; the replicated lockstep in
    # repro.sim.phy_driver) must apply the SAME eval schedule and
    # budget-stop rule or the R=1 bit-for-bit parity contract breaks —
    # one definition each.
    def eval_due(self, t: int) -> bool:
        return t % self.fl.eval_every == 0 or t == self.fl.T

    def budget_spent(self, cum_latency: float) -> bool:
        return (self.fl.latency_budget_s is not None
                and cum_latency >= self.fl.latency_budget_s)

    def eval_accuracy_replicated(self, state: ReplicatedRunState,
                                 alive: Optional[np.ndarray] = None
                                 ) -> np.ndarray:
        """Test accuracy per replicate [R] (NaN for replicates the
        ``alive`` mask excludes — nobody logs them anymore).
        The spec's accuracy fn is a host minibatch loop, so replicates
        evaluate one at a time — for R = 1 this is the identical call
        the unreplicated path makes (the bit-for-bit parity contract
        covers accuracy too)."""
        accuracy = self.model_spec.accuracy
        accs = np.full(state.R, np.nan)
        rs = range(state.R) if alive is None else np.flatnonzero(alive)
        with _obs.scope("eval"):
            for r in rs:
                accs[r] = accuracy(self.replicate_params(state, int(r)),
                                   state.test_x, state.test_y)
        return accs

    def solve_uplink_host(self, chan: Optional[ChannelRealization],
                          bits_np: np.ndarray, active: np.ndarray
                          ) -> "UplinkSolution":
        """Stage 3 (host reference path): per-cell numpy power solve.

        Returns an :class:`UplinkSolution` always carrying the per-user
        upload-completion times scattered back to the full user axis
        (0 for absent users) — the async event clock's input.  The
        NamedTuple unpacks as the legacy ``(straggler_s, per_user_s)``
        pair."""
        per_user = np.zeros(self.K)
        if self.power is None or chan is None:
            return UplinkSolution(0.0, per_user)
        act_idx = np.flatnonzero(active)
        if len(act_idx) == 0:
            # async corner: every participating user is mid-upload, so
            # nobody transmits fresh payload this round
            return UplinkSolution(0.0, per_user)
        with _obs.scope("engine.solve"):
            if len(act_idx) == self.K:
                sol = self.power.solve(chan, np.maximum(bits_np, 1.0))
                per_user = np.asarray(sol.latencies, np.float64)
            else:
                # churn: only active users transmit — solve the
                # power-control problem on the sub-channel so
                # absent users neither get power nor interfere
                sol = self.power.solve(
                    _subchannel(chan, act_idx),
                    np.maximum(bits_np[act_idx], 1.0))
                per_user[act_idx] = np.asarray(sol.latencies, np.float64)
        return UplinkSolution(sol.straggler_latency, per_user)

    def solve_uplink_host_detailed(
            self, chan: Optional[ChannelRealization],
            bits_np: np.ndarray, active: np.ndarray
            ) -> Tuple[float, np.ndarray]:
        """DEPRECATED alias of :meth:`solve_uplink_host`, which now
        returns the full :class:`UplinkSolution` itself."""
        warnings.warn(
            "solve_uplink_host_detailed is deprecated; "
            "solve_uplink_host now returns an UplinkSolution carrying "
            "both straggler_s and latencies", DeprecationWarning,
            stacklevel=2)
        return self.solve_uplink_host(chan, bits_np, active)

    # -------------------------------------------------- async complete
    def _advance_clock(self, clock: AsyncClock, active: np.ndarray,
                       participating: np.ndarray, ell: np.ndarray
                       ) -> Tuple[AsyncClockStep, AsyncRoundInfo]:
        """Run the host event clock and fold the transition into the
        clock's host state + cumulative drop counters.  All inputs
        leading-batched [B, K]."""
        step = advance_async_clock(
            clock.in_flight, clock.remaining_s, clock.staleness, ell,
            active, participating, self.rho, self.engine_cfg.staleness)
        clock.in_flight = step.in_flight
        clock.remaining_s = step.remaining_s
        clock.staleness = step.staleness
        clock.dropped_stale += int(step.dropped_stale.sum())
        clock.dropped_churn += int(step.dropped_churn.sum())
        clock.arrived_total += int(step.arrived.sum())
        n_arr = step.arrived.sum(axis=-1)
        stale_sum = step.arrived_staleness.sum(axis=-1)
        info = AsyncRoundInfo(
            round_uplink_s=step.round_s,
            n_arrived=n_arr,
            mean_staleness=np.divide(
                stale_sum, n_arr, out=np.zeros_like(step.round_s),
                where=n_arr > 0),
            max_staleness_obs=step.arrived_staleness.max(axis=-1),
            straggler_gap_s=step.straggler_gap_s,
            dropped_stale=step.dropped_stale,
            dropped_churn=step.dropped_churn,
            effective_participation=n_arr / float(self.K),
            in_flight_next=step.in_flight.sum(axis=-1))
        return step, info

    def complete_round_async(self, state: RunState, work: RoundWork,
                             per_user_s: np.ndarray) -> AsyncRoundInfo:
        """Async stage 3.5: host event clock + the jitted
        aggregate+buffer-shuffle dispatch.  MUST be called on the
        TRAINING state (the one ``train_round`` advanced) — it updates
        ``state.params``; ``finish_round`` never aggregates."""
        clock = state.async_clock
        step, info = self._advance_clock(
            clock, work.active[None], work.participating[None],
            np.asarray(per_user_s, np.float64)[None])
        _, agg_step = self._async_steps(None)
        f32 = lambda a: jnp.asarray(a[0], jnp.float32)
        state.params, clock.buffer = agg_step(
            state.params, clock.payload, clock.buffer,
            f32(step.w_fresh), f32(step.w_buf),
            f32(step.move), f32(step.keep))
        clock.payload = None
        self._record_async(work.t, info)
        return info

    def _record_async(self, t: int, info: AsyncRoundInfo) -> None:
        if not _obs.enabled():
            return
        _obs.record(
            "engine.async", round=t,
            round_uplink_s=float(np.mean(info.round_uplink_s)),
            arrived=float(np.mean(info.n_arrived)),
            mean_staleness=float(np.mean(info.mean_staleness)),
            max_staleness=int(np.max(info.max_staleness_obs)),
            straggler_gap_s=float(np.mean(info.straggler_gap_s)),
            dropped_stale=int(np.sum(info.dropped_stale)),
            dropped_churn=int(np.sum(info.dropped_churn)),
            effective_participation=float(
                np.mean(info.effective_participation)),
            in_flight=float(np.mean(info.in_flight_next)))

    def finish_round(self, state: RunState, work: RoundWork,
                     uplink: float, verbose: bool = False,
                     async_info: Optional[AsyncRoundInfo] = None,
                     per_user_s: Optional[np.ndarray] = None,
                     power_fallbacks: int = 0) -> bool:
        """Stage 4: latency accounting, eval, logging.  Returns False
        once the latency budget is exhausted (stop stepping).

        Never aggregates — async callers run ``complete_round_async``
        first and pass its ``async_info`` here, so the latency/budget
        burn-down uses the async event clock (the round costs the
        deadline the server actually waited, not the slowest user), and
        the log rows carry staleness/arrival columns.  ``per_user_s``
        (sync path) feeds the straggler-gap metric."""
        from repro.fl.loop import RoundLog

        t = work.t
        if async_info is not None:
            uplink = float(async_info.round_uplink_s[0])
            gap = float(async_info.straggler_gap_s[0])
            eff = float(async_info.effective_participation[0])
            stale = float(async_info.mean_staleness[0])
            dropped = int(async_info.dropped_stale[0]
                          + async_info.dropped_churn[0])
        else:
            gap = 0.0 if per_user_s is None \
                else straggler_gap(per_user_s, work.active)
            eff = float(np.sum(work.active > 0)) / self.K
            stale, dropped = 0.0, 0
        state.cum_latency += uplink + self.comp_lat
        acc = None
        if self.eval_due(t):
            with _obs.scope("eval"):
                acc = self.model_spec.accuracy(state.params, state.test_x,
                                               state.test_y)
        quarantined = int(getattr(work, "quarantined", 0) or 0)
        state.logs.append(RoundLog(t, work.bits_np, uplink,
                                   self.comp_lat, state.cum_latency,
                                   work.mean_s, acc,
                                   straggler_gap_s=gap,
                                   mean_staleness=stale,
                                   effective_participation=eff,
                                   dropped_uploads=dropped,
                                   quarantined_users=quarantined,
                                   power_fallbacks=int(power_fallbacks)))
        state.rounds_done = t
        if _obs.enabled() and (quarantined or power_fallbacks):
            _obs.record("resilience.quarantine", t=t,
                        quarantined_users=quarantined,
                        power_fallbacks=int(power_fallbacks))
        self._log_round(t, acc, work, uplink, state.cum_latency,
                        verbose, gap=gap)
        return not self.budget_spent(state.cum_latency)

    def _log_round(self, t: int, acc, work, uplink: float,
                   cum_latency: float, verbose: bool,
                   gap: float = 0.0) -> None:
        """Round logging: every round goes to the active obs session;
        the console line (the quickstart's old ``print``) appears only
        under verbose, throttled by EngineConfig.log_every."""
        ecfg = self.engine_cfg
        if _obs.enabled():
            budget = self.fl.latency_budget_s
            _obs.record(
                "engine.round", t=t,
                acc=None if acc is None else float(acc),
                bits_mean=float(work.bits_np.mean()),
                uplink_s=float(uplink), comp_s=float(self.comp_lat),
                cum_latency_s=float(cum_latency),
                mean_s=float(work.mean_s),
                active_users=int(np.sum(work.active > 0)),
                straggler_gap_s=float(gap),
                budget_remaining_s=None if budget is None
                else float(budget - cum_latency))
        if (verbose or ecfg.verbose) and acc is not None:
            every = max(1, ecfg.log_every)
            if (t // self.fl.eval_every) % every == 0 or t == self.fl.T:
                print(f"[round {t:4d}] acc={acc:.4f} "
                      f"bits/user={work.bits_np.mean():.3e} "
                      f"cum_lat={cum_latency:.2f}s")

    def result(self, state: RunState):
        from repro.fl.loop import FLResult
        return FLResult(params=state.params, logs=state.logs,
                        rounds_completed=state.rounds_done)

    def run(self, verbose: bool = False):
        async_on = self.engine_cfg.async_active
        state = self.start_run()
        for t in range(1, self.fl.T + 1):
            with _obs.round_scope(t, quantizer=self.quantizer.name):
                with _obs.scope("train_round") as sc:
                    work = self.train_round(state, t)
                    sc.block(state.params)
                with _obs.scope("solve_uplink"):
                    uplink, per_user = self.solve_uplink_host(
                        state.chan, work.bits_np, work.active)
                info = None
                if async_on:
                    with _obs.scope("complete_async"):
                        info = self.complete_round_async(state, work,
                                                         per_user)
                with _obs.scope("finish_round"):
                    more = self.finish_round(state, work, uplink,
                                             verbose=verbose,
                                             async_info=info,
                                             per_user_s=per_user)
            if not more:
                break
        return self.result(state)
