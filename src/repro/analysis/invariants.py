"""The declarative per-lane invariant manifest the HLO pass enforces.

Every serving lane (a valid :class:`repro.api.ServeConfig` point) carries a
:class:`LaneInvariant`: which device program it compiles, how many
nearest-neighbor collectives that program may contain, which ops are
forbidden outright, and the dtype/host-transfer policy. The manifest is the
checkable form of the architecture prose in docs/architecture.md:

  * sharded predict is HALO-SHAPED — the composed reverse halo is 4
    ppermutes (row exchange + column exchange of the slot-flipped results);
    the budget of 8 leaves headroom for a second composed exchange but is
    far below the 36 per-slot hops the PR-2 program paid;
  * the cache NEVER moves — no all-gather / all-reduce / reduce-scatter /
    all-to-all anywhere in a serving program (the decentralized-serving
    claim, arXiv 1402.1472-style: ship low-rank summaries once, never
    re-aggregate);
  * replicated predict is mesh-free — ZERO collectives of any kind;
  * serving math is f32 — an f64 leak doubles halo bytes and falls off the
    TPU fast path silently;
  * no host transfers inside a compiled serving program — a callback or
    infeed would stall the overlapped pipeline for a full device window
    (the ``device_put``-inside-``route`` bug class, at the HLO level).

Lanes that share a device program (pipeline/router only change HOST-side
scheduling) point at the same ``program`` key; the HLO pass lowers each
distinct program once and applies every lane's invariant to its text, so a
future divergence between two lanes' programs is caught the moment someone
introduces one.

Stdlib-only: the manifest must be importable (and testable) without jax.
"""
from __future__ import annotations

import dataclasses

# Collective mnemonics as they appear in StableHLO / HLO text. The dashed
# and underscored spellings are both matched by the HLO pass.
COLLECTIVE_OPS = (
    "collective-permute",
    "all-gather",
    "all-reduce",
    "all-to-all",
    "reduce-scatter",
)

# Ops that move data between host and device inside a compiled program.
HOST_TRANSFER_OPS = (
    "infeed",
    "outfeed",
    "send",
    "recv",
    "xla_python_cpu_callback",
    "xla_python_gpu_callback",
    "xla_ffi_python",
    "host_callback",
)

# The factors-never-move claim: nothing may re-aggregate sharded state.
GATHERING_COLLECTIVES = ("all-gather", "all-reduce", "all-to-all", "reduce-scatter")

# Composed reverse halo = 4 ppermutes; budget 8 leaves room for one more
# composed exchange (e.g. a future low-rank global term) but stays an
# order below the 36 per-slot hops the pre-composition program paid.
PPERMUTE_BUDGET = 8


@dataclasses.dataclass(frozen=True)
class LaneInvariant:
    """What one serving lane's compiled program is allowed to contain.

    Fields:
      name: stable lane id, e.g. "sharded/pipelined/two-level/fused".
      serve: the ServeConfig dict of the lane (validated against
        ``repro.api.ServeConfig.from_dict`` by the HLO pass, so manifest
        rot — a field rename, an illegal combination — fails the pass).
      program: device-program key the HLO pass lowers —
        "replicated-blend" | "sharded-blend".
      backend: kernel lane the program is built with ("ref"|"pallas"|
        "fused"); with ``program="replicated-blend"`` must be "ref".
      max_collective_permute: inclusive ppermute budget.
      min_collective_permute: floor — a sharded program with FEWER is just
        as wrong (the halo vanished, or the linter stopped seeing it; the
        floor is what catches a rotted op-matching pattern).
      forbidden_ops: op mnemonics that must not appear at all.
      forbid_f64 / forbid_host_transfer: dtype and host-transfer policy.
    """

    name: str
    serve: dict
    program: str
    backend: str
    max_collective_permute: int
    forbidden_ops: tuple
    min_collective_permute: int = 0
    forbid_f64: bool = True
    forbid_host_transfer: bool = True

    def __post_init__(self) -> None:
        if self.program not in ("replicated-blend", "sharded-blend"):
            raise ValueError(f"unknown program {self.program!r} for lane {self.name!r}")
        if self.backend not in ("ref", "pallas", "fused"):
            raise ValueError(f"unknown backend {self.backend!r} for lane {self.name!r}")
        if self.program == "replicated-blend" and self.backend != "ref":
            raise ValueError(f"replicated lanes have no kernel lane (lane {self.name!r})")
        if self.max_collective_permute < 0:
            raise ValueError(f"negative ppermute budget for lane {self.name!r}")
        if not 0 <= self.min_collective_permute <= self.max_collective_permute:
            raise ValueError(f"bad ppermute floor for lane {self.name!r}")
        unknown = set(self.forbidden_ops) - set(COLLECTIVE_OPS)
        if unknown:
            raise ValueError(f"unknown forbidden ops {sorted(unknown)} for lane {self.name!r}")

    @property
    def program_key(self) -> tuple:
        """(program, backend): lanes sharing it share one lowered text."""
        return (self.program, self.backend)


def _sharded_lanes() -> tuple:
    lanes = []
    for pipeline in ("serial", "pipelined"):
        for router in ("single", "two-level"):
            for backend in ("ref", "pallas", "fused"):
                lanes.append(
                    LaneInvariant(
                        name=f"sharded/{pipeline}/{router}/{backend}",
                        serve={
                            "mode": "sharded",
                            "pipeline": pipeline,
                            "router": router,
                            "backend": backend,
                        },
                        program="sharded-blend",
                        backend=backend,
                        max_collective_permute=PPERMUTE_BUDGET,
                        min_collective_permute=4,
                        forbidden_ops=GATHERING_COLLECTIVES,
                    )
                )
    # the fixed-q_max whole-stream-prepass lane (sharded single-router)
    lanes.append(
        LaneInvariant(
            name="sharded/serial/single/ref/fixed-q_max",
            serve={"mode": "sharded", "backend": "ref", "q_max": 64},
            program="sharded-blend",
            backend="ref",
            max_collective_permute=PPERMUTE_BUDGET,
            min_collective_permute=4,
            forbidden_ops=GATHERING_COLLECTIVES,
        )
    )
    return tuple(lanes)


LANES: tuple = (
    LaneInvariant(
        name="replicated/serial/single/ref",
        serve={"mode": "replicated", "backend": "ref"},
        program="replicated-blend",
        backend="ref",
        max_collective_permute=0,
        forbidden_ops=COLLECTIVE_OPS,
    ),
) + _sharded_lanes()


# --------------------------------------------------------------------------
# Compiled-cost budgets (the ``costs`` pass)
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class CostBudget:
    """The compiled-cost envelope of one device program.

    The costs pass AOT-compiles each program at several scale points,
    reads XLA's ``cost_analysis()`` / ``memory_analysis()``, fits log-log
    scaling exponents, and enforces:

      * COST-FLOP-SUPERLINEAR — flops must be (near-)linear in the query
        axis (``scale_axis``): fitted exponent <= ``max_flop_exponent``.
        A pairwise/quadratic term sneaking into the blend shows up as an
        exponent near 2 long before any benchmark feels it.
      * COST-MEM-SCALING — compiled SPMD stats are PER DEVICE, so the
        1/P cache-residency claim is simply "per-device argument bytes
        and flops are FLAT as the mesh grows": fitted exponent vs the
        device count <= ``max_device_exponent``. A replicated cache in
        the in_specs makes per-device bytes GROW with P and is caught
        here (sharded programs only).
      * COST-BUDGET — absolute ceilings at the ``anchor`` scale point
        (~2.5-3x headroom over the measured program, so real regressions
        gate while compiler noise does not).

    Stdlib-only, like the lane manifest above.
    """

    program: str  # "replicated-blend" | "sharded-blend"
    scale_axis: str  # axis the flop exponent is fitted against
    anchor: str  # point label the absolute ceilings apply at
    max_flop_exponent: float
    max_flops: float
    max_bytes_accessed: float
    max_arg_bytes: int
    max_temp_bytes: int
    max_device_exponent: float | None = None  # sharded only: vs device count

    def __post_init__(self) -> None:
        if self.program not in ("replicated-blend", "sharded-blend"):
            raise ValueError(f"unknown program {self.program!r} in cost budget")
        if not 1.0 <= self.max_flop_exponent < 2.0:
            # linear is the claim; an allowance at or past quadratic
            # would make the rule vacuous
            raise ValueError(f"flop exponent budget must be in [1, 2) for {self.program!r}")
        if self.max_device_exponent is not None and not 0.0 <= self.max_device_exponent < 1.0:
            raise ValueError(f"device exponent budget must be in [0, 1) for {self.program!r}")
        for field in ("max_flops", "max_bytes_accessed", "max_arg_bytes", "max_temp_bytes"):
            if getattr(self, field) <= 0:
                raise ValueError(f"{field} must be positive for {self.program!r}")


COST_BUDGETS: dict = {
    # jit blend over the full replicated cache; scale points sweep
    # n_queries. ~0.6 Mflop / 1.5 MB accessed measured at n=256.
    # Temp bytes: jax 0.9.0's XLA:CPU keeps all eight per-corner gathered
    # W/U factor tiles (n x m x m f32 each) live at once — 596 KB at n=256,
    # 4x what older XLA scheduled for the same HLO. Still linear in n; the
    # ceiling keeps ~2.6x headroom over that.
    "replicated-blend": CostBudget(
        program="replicated-blend",
        scale_axis="n_queries",
        anchor="n=256",
        max_flop_exponent=1.3,
        max_flops=2.0e6,
        max_bytes_accessed=5.0e6,
        max_arg_bytes=131072,
        max_temp_bytes=1572864,
    ),
    # shard_map blend, one partition per device; scale points sweep the
    # grid side (device exponent) and q_max (flop exponent). Per-device
    # ~0.22 Mflop / 0.27 MB accessed / 7.3 KB args measured at the
    # (grid=4, q=64) anchor — flat across P by construction.
    "sharded-blend": CostBudget(
        program="sharded-blend",
        scale_axis="q_max",
        anchor="grid=4/q=64",
        max_flop_exponent=1.3,
        max_flops=7.0e5,
        max_bytes_accessed=9.0e5,
        max_arg_bytes=24576,
        max_temp_bytes=262144,
        max_device_exponent=0.3,
    ),
}
