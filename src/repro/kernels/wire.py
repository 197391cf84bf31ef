"""WirePath — the single wire-path specification shared by sim and dist.

Before PR 8 the wire-path choice was spread over three stringly-typed
knobs that named the SAME underlying decision in different vocabularies:

* ``EngineConfig.aggregation`` — ``"dense" | "signplane" | "wire"``
  (which aggregation plane the sim engine's fused step runs);
* ``CompressorConfig.wire_path`` — ``"fused" | "reference"`` (which
  realization of the packed exchange repro.dist runs);
* per-call ``interpret`` / ``use_kernel`` picks in ``kernels/ops.py``
  (which lowering of the packed plane executes).

:class:`WirePath` owns all three axes in one frozen spec, plus the
streaming-cohort knobs introduced with it:

* ``plane``    — what moves at the fan-in: ``"dense"`` f32 recons,
  ``"signplane"`` packed 1-bit planes + dense high-res correction, or
  ``"packed"`` the full sign/hi/code wire buffers (DESIGN.md §9);
* ``lowering`` — which implementation of the packed plane runs:
  ``"auto"`` (Pallas kernels on TPU, the jnp ref-oracle composition
  elsewhere — today's default behaviour), ``"kernel"``, ``"reference"``;
* ``reduce``   — how multi-peer buffers meet in repro.dist manual mode:
  ``"gather"`` (all_gather the packed buffers, one fused decode) or
  ``"ring"`` (G-1 ``collective_permute`` hops, one packed buffer
  resident per hop, folded via the chunked accumulate — DESIGN.md §12);
* ``cohort_size`` — sim engine user-axis streaming: ``None`` keeps the
  fully vectorized step (bit-for-bit today's path); an int C scans the
  K users in cohorts of C so no ``[K, d]`` buffer ever exists;
* ``clusters`` — two-level hierarchy: the K users are partitioned into
  this many AP-cluster groups, each aggregated on-device into a partial
  ``[d]`` plane, combined host-side (the cell-free topology's sharding
  story for the 10^4-10^5-user axis).

The legacy strings keep working through :func:`from_aggregation` /
:func:`from_wire_path` (DeprecationWarning; tests/test_cohort.py pins
the shims).
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Optional

import jax

PLANES = ("dense", "signplane", "packed")
LOWERINGS = ("auto", "kernel", "reference")
REDUCES = ("gather", "ring")

# The packed wire format counts high-res entries (dbar) and folds the
# weighted dequant in f32 accumulators: exact only while every integer
# involved stays below 2**24 (f32 mantissa).  Shared guard — the sim
# engine constructor, the fused encoder, and repro.dist's
# CompressorConfig paths all call it so large-d misuse fails loudly
# everywhere instead of silently miscounting.
PACKED_DIM_LIMIT = 2 ** 24


def check_packed_dim(d: int, *, where: str = "the packed wire plane"
                     ) -> None:
    """Raise unless ``d`` is exactly countable in the f32 wire headers."""
    if d >= PACKED_DIM_LIMIT:
        raise ValueError(
            f"{where} requires d < 2**24 (got d={d}): the dbar count and "
            "weighted dequant accumulate in f32, which is exact only below "
            "2**24. Shard the vector (repro.dist), use per-layer budget "
            "segments under 2**24 each, or the signplane/dense plane.")

# legacy vocabulary -> plane
_AGGREGATION_TO_PLANE = {"dense": "dense", "signplane": "signplane",
                         "wire": "packed"}
_WIRE_PATH_TO_PLANE = {"fused": "packed", "reference": "signplane"}


@dataclasses.dataclass(frozen=True)
class WirePath:
    """One wire-path spec for both the sim engine and repro.dist."""
    plane: str = "packed"        # "dense" | "signplane" | "packed"
    lowering: str = "auto"       # "auto" | "kernel" | "reference"
    reduce: str = "gather"       # "gather" | "ring" (dist manual mode)
    cohort_size: Optional[int] = None    # sim: stream K in cohorts of C
    clusters: int = 1            # sim: AP-cluster partial aggregates
    # Optional repro.core.quantize.LayerBudget — per-leaf-group
    # mixed-resolution budgets (DESIGN.md §13).  Typed loosely to keep
    # kernels import-independent of core.quantize; validate() duck-checks
    # the contract.  LayerBudget.uniform() (is_uniform=True) must behave
    # exactly like None: consumers keep the single-segment global path.
    budget: Optional[object] = None
    # Stamp an xor-fold integrity word over the packed uint32 planes
    # into header lane H_CHK at encode, verified at decode by the
    # resilience layer (DESIGN.md §14).  Stamping touches no lane the
    # decode or bit accounting reads, so checksum=True alone is
    # bit-for-bit on params, payload bits and metrics.
    checksum: bool = False

    def __post_init__(self):
        self.validate()

    def validate(self) -> None:
        if self.plane not in PLANES:
            raise ValueError(f"unknown wire plane {self.plane!r}; "
                             f"have {PLANES}")
        if self.lowering not in LOWERINGS:
            raise ValueError(f"unknown wire lowering {self.lowering!r}; "
                             f"have {LOWERINGS}")
        if self.reduce not in REDUCES:
            raise ValueError(f"unknown wire reduce {self.reduce!r}; "
                             f"have {REDUCES}")
        if self.cohort_size is not None and self.cohort_size < 1:
            raise ValueError(
                f"cohort_size must be >= 1 or None, got {self.cohort_size}")
        if self.clusters < 1:
            raise ValueError(f"clusters must be >= 1, got {self.clusters}")
        if self.cohort_size is not None and self.plane != "packed":
            raise ValueError(
                "cohort streaming folds packed wire planes; use "
                f"plane='packed' (got plane={self.plane!r})")
        if self.clusters > 1 and self.cohort_size is None:
            raise ValueError(
                "clusters > 1 partially aggregates cohort streams; set "
                "cohort_size as well")
        if self.budget is not None and not (
                hasattr(self.budget, "segments_for")
                and hasattr(self.budget, "is_uniform")):
            raise ValueError(
                "budget must be a repro.core.quantize.LayerBudget "
                f"(got {type(self.budget).__name__})")
        if self.checksum and self.plane != "packed":
            raise ValueError(
                "checksum folds the packed uint32 wire planes; use "
                f"plane='packed' (got plane={self.plane!r})")
        if self.budget is not None and not self.budget.is_uniform:
            if self.plane == "signplane":
                raise ValueError(
                    "per-layer budgets are not supported on the signplane "
                    "plane; use plane='packed' or plane='dense'")
            if self.streaming or self.clusters > 1:
                raise ValueError(
                    "per-layer budgets do not compose with cohort "
                    "streaming or AP clusters yet; drop cohort_size/"
                    "clusters or use LayerBudget.uniform()")

    @property
    def effective_budget(self):
        """The budget when it changes anything, else None — uniform
        budgets route the pre-existing global path bit-for-bit."""
        if self.budget is not None and not self.budget.is_uniform:
            return self.budget
        return None

    # ------------------------------------------------ lowering resolution
    def use_kernel(self) -> bool:
        """True when the packed plane runs the Pallas kernels (the TPU
        target); False runs the jnp ref-oracle composition under the
        caller's jit — what CPU call sites actually execute."""
        if self.lowering == "auto":
            return jax.default_backend() == "tpu"
        return self.lowering == "kernel"

    def interpret(self) -> bool:
        """Pallas interpret mode: the CPU correctness harness for the
        kernel lowering.  True only when the kernels run
        (:meth:`use_kernel`) on a backend that is not a TPU; on a TPU
        the compiled kernels always run."""
        return self.use_kernel() and jax.default_backend() != "tpu"

    @property
    def streaming(self) -> bool:
        """True when the sim engine scans user cohorts instead of
        vectorizing the full K axis."""
        return self.cohort_size is not None


def from_aggregation(name: str, *, warn: bool = True) -> WirePath:
    """Map a legacy ``EngineConfig.aggregation`` string to a WirePath.

    ``warn=True`` emits the deprecation warning (the shim for old call
    sites); resolvers that merely translate a still-supported default
    pass ``warn=False``."""
    if name not in _AGGREGATION_TO_PLANE:
        raise ValueError(f"unknown aggregation {name!r}; "
                         f"have {tuple(_AGGREGATION_TO_PLANE)}")
    if warn:
        warnings.warn(
            f"EngineConfig.aggregation={name!r} is deprecated; pass "
            f"EngineConfig(wire=WirePath(plane="
            f"{_AGGREGATION_TO_PLANE[name]!r}))",
            DeprecationWarning, stacklevel=2)
    return WirePath(plane=_AGGREGATION_TO_PLANE[name])


def from_wire_path(name: str, *, warn: bool = True) -> WirePath:
    """Map a legacy ``CompressorConfig.wire_path`` string to a WirePath."""
    if name not in _WIRE_PATH_TO_PLANE:
        raise ValueError(f"unknown wire_path {name!r}; "
                         f"have {tuple(_WIRE_PATH_TO_PLANE)}")
    if warn:
        warnings.warn(
            f"CompressorConfig.wire_path={name!r} is deprecated; pass "
            f"CompressorConfig(wire=WirePath(plane="
            f"{_WIRE_PATH_TO_PLANE[name]!r}))",
            DeprecationWarning, stacklevel=2)
    return WirePath(plane=_WIRE_PATH_TO_PLANE[name])
