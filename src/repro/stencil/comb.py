"""Comb-style benchmark driver: barriered, multi-cycle halo-exchange timing.

Follows the paper's measurement protocol (§V): synchronize before timing, run
many exchange cycles, extract the average per-cycle cost, repeat the whole
measurement several times and average.  On this CPU container the *measured*
numbers capture real pack/update compute and the python/dispatch overhead gap
between standard and persistent; the network projection for cluster scales
comes from ``repro.core.model_comm`` (benchmarks/fig*.py).

Strategies are resolved through the registry in
:mod:`repro.stencil.strategies`; ``comb_measure`` accepts either names or
fully-typed :class:`~repro.stencil.strategies.StrategyConfig` values, so a
newly registered strategy is benchmarkable without touching this module.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable

import jax
import numpy as np

from repro.stencil.domain import Domain
from repro.stencil.strategies import (
    ExchangeStrategy,
    StrategyConfig,
    make_driver,
)


def _mean_checksum(x: jax.Array) -> float:
    """Mean of the (possibly multi-process) stored array, on every rank.

    On a ``jax.distributed`` grid op-by-op numpy conversion of a
    non-addressable global array is illegal; a jitted fully-replicated
    reduction gives every rank the identical scalar, so the cross-strategy
    divergence check below stays meaningful across processes.
    """
    if getattr(x, "is_fully_addressable", True):
        return float(np.asarray(jax.numpy.mean(x)))
    from jax.sharding import NamedSharding, PartitionSpec

    out = jax.jit(
        jax.numpy.mean,
        out_shardings=NamedSharding(x.sharding.mesh, PartitionSpec()),
    )(x)
    return float(np.asarray(out))


@dataclasses.dataclass
class CycleResult:
    strategy: str
    us_per_cycle: float
    init_us: float
    n_cycles: int
    repeats: int
    checksum: float
    n_parts: int = 1
    packer: str = "slice"
    transport: str = "ppermute"
    coalesce: bool = True
    #: process-to-node placement the mesh was built under (repro.launch.
    #: mapping) — the §VI mapping axis, stamped from the driver's config
    mapping: str = "row-major"
    #: collectives ONE step launches (coalescing's one-per-neighbor claim,
    #: verified against compiled HLO by tests/core/test_coalesce.py)
    collective_count: int | None = None
    #: persistent-plan amortization counters for THIS measurement's init
    #: (hits > 0 means setup was skipped — the paper's amortized case)
    plan_cache_inits: int = 0
    plan_cache_hits: int = 0
    #: time to re-derive the full static transport schedule (Message tables
    #: + WireLayout offsets) for the current topology — what an elastic
    #: re-mesh pays *besides* the recompile; static offsets keep it cheap
    replan_us: float = 0.0
    #: plans this measurement's cache dropped to a topology change (zero in
    #: a steady-state sweep; the elastic runner drives it up)
    plan_cache_invalidations: int = 0
    #: autotune provenance when the driver resolved an "auto" cell
    #: ("trace"/"trace-nearest"/"model"/"calibration"/"cache"); None for
    #: hand-pinned cells, whose strategy/packer/coalesce ARE the request
    selected_by: str | None = None
    #: the tuner's score for the chosen cell (recorded us for trace
    #: verdicts, modeled/probed us otherwise); None for pinned cells
    predicted_us: float | None = None
    #: wall time the in-situ calibration probes cost (0 when the verdict
    #: came from a trace, the model, or the persistent autotune cache)
    calibration_us: float = 0.0
    #: how membership churn was (or would be) recovered during this
    #: measurement: "none" for steady-state sweep cells, "relaunch" /
    #: "in-grid" when the elastic runner produced the record
    #: (repro.launch.elastic)
    recovery_mode: str = "none"
    #: total µs spent moving LIVE state onto grown meshes for rank JOINs
    #: (0.0 when no rank joined — every steady-state cell)
    join_us: float = 0.0
    #: ranks that kept their process + warm plan cache through the last
    #: membership change (0 in steady state and after any relaunch)
    warm_ranks: int = 0

    def record(self) -> dict:
        """Flat, json-serializable form (the BENCH_*.json row body)."""
        return dataclasses.asdict(self)


def run_cycles(
    driver: ExchangeStrategy,
    x: jax.Array,
    *,
    n_cycles: int = 50,
    warmup: int = 3,
    repeats: int = 3,
) -> CycleResult:
    """Time ``n_cycles`` exchange(+update) iterations, paper-style.

    ``init_us`` is the measured one-time setup (trace+lower+compile) and is
    only charged to strategies declaring ``amortizes_init`` (no-op inits
    would otherwise record timer noise).  The plan-cache hit/miss delta of
    this init and the step's scheduled collective count ride along in the
    result, so BENCH records can show the persistent-amortization and
    message-coalescing effects directly.
    """
    cache = driver.config.resolve_cache()
    hits0, inits0, invals0 = (
        (cache.stats.cache_hits, cache.stats.inits,
         cache.stats.invalidations) if cache else (0, 0, 0)
    )
    t0 = time.perf_counter()
    driver.init(x)
    init_us = (time.perf_counter() - t0) * 1e6
    if not driver.amortizes_init:
        init_us = 0.0
    if cache is not None:
        plan_hits = cache.stats.cache_hits - hits0
        plan_inits = cache.stats.inits - inits0
        plan_invals = cache.stats.invalidations - invals0
    else:  # private plan: one init when the strategy amortizes, never a hit
        plan_hits, plan_inits, plan_invals = 0, int(driver.amortizes_init), 0
    try:
        collective_count = driver.scheduled_collectives(x)
    except NotImplementedError:
        collective_count = None
    # the elastic re-plan cost: re-deriving the static Message/WireLayout
    # tables for this topology from scratch (table math only — no compile)
    t0 = time.perf_counter()
    driver.replan_tables(x)
    replan_us = (time.perf_counter() - t0) * 1e6

    for _ in range(warmup):
        x = driver.step(x)
    driver.wait(x)

    times = []
    for _ in range(repeats):
        driver.wait(x)  # the paper's pre-timing barrier
        t0 = time.perf_counter()
        for _ in range(n_cycles):
            x = driver.step(x)
        driver.wait(x)  # Waitall before stopping the clock
        times.append((time.perf_counter() - t0) / n_cycles * 1e6)
    checksum = _mean_checksum(x)
    return CycleResult(
        strategy=driver.strategy,
        us_per_cycle=float(np.mean(times)),
        init_us=init_us,
        n_cycles=n_cycles,
        repeats=repeats,
        checksum=checksum,
        n_parts=driver.n_parts,
        packer=driver.config.packer,
        transport=driver.config.transport,
        coalesce=driver.config.coalesce,
        mapping=driver.config.mapping,
        collective_count=collective_count,
        plan_cache_inits=plan_inits,
        plan_cache_hits=plan_hits,
        replan_us=replan_us,
        plan_cache_invalidations=plan_invals,
        # autotuned drivers expose their selection provenance; pinned
        # drivers have none (getattr: only AutoStrategy defines these)
        selected_by=getattr(driver, "selected_by", None),
        predicted_us=getattr(driver, "predicted_us", None),
        calibration_us=getattr(driver, "calibration_us", 0.0),
    )


def _as_config(
    strategy: str | StrategyConfig, default_n_parts: int
) -> StrategyConfig:
    if isinstance(strategy, StrategyConfig):
        return strategy
    if strategy == "auto":
        # the bare name opens every autotunable axis; pass an explicit
        # StrategyConfig to pin packer/coalesce while tuning the rest
        return StrategyConfig(name="auto", packer="auto", coalesce="auto")
    n_parts = default_n_parts if strategy == "partitioned" else 1
    return StrategyConfig(name=strategy, n_parts=n_parts)


def result_label(name: str, packer: str = "slice",
                 coalesce: bool = True) -> str:
    """The one definition of ``comb_measure``'s result-key convention:
    the strategy name, suffixed ``@packer`` for non-default packers (the
    §VI packing axis) and ``~uncoalesced`` for the coalesce-off baseline
    cells.  Callers resolving a measurement by name — e.g. the sweep's
    baseline lookup — must build the key through this."""
    label = name if packer == "slice" else f"{name}@{packer}"
    return label if coalesce else f"{label}~uncoalesced"


def comb_measure(
    domain: Domain,
    *,
    strategies: tuple[str | StrategyConfig, ...] = (
        "standard", "persistent", "partitioned",
    ),
    n_parts: int = 4,
    update_fn: Callable[[jax.Array], jax.Array] | None = None,
    n_cycles: int = 50,
    repeats: int = 3,
    seed: int = 0,
    make_input: Callable[[], jax.Array] | None = None,
) -> dict[str, CycleResult]:
    """Measure all strategies on one domain; checksums must agree.

    ``n_parts`` is the default partition count applied to strategies named
    ``"partitioned"``; pass explicit :class:`StrategyConfig` values to pin
    per-strategy knobs (partition count, packer, plan-cache policy).
    Results are keyed by strategy name, suffixed ``@packer`` for non-default
    packers (the §VI packing axis); when the same key is swept more than
    once (e.g. partitioned at several partition counts) later entries get a
    ``name#pN`` key — and a ``#2``/``#3`` ordinal when name *and* partition
    count repeat — so no measurement is silently dropped.  ``make_input``
    builds each strategy's fresh input (default ``domain.random(seed)``);
    a caller whose data is large builds it once and hands each strategy a
    device copy.
    """
    results: dict[str, CycleResult] = {}
    for strategy in strategies:
        config = _as_config(strategy, n_parts)
        label = result_label(config.name, config.packer, config.coalesce)
        if label in results:
            label = f"{label}#p{config.n_parts}"
        if label in results:
            # same name AND same n_parts swept again (e.g. cache-policy
            # A/B runs): stable ordinal suffix instead of dropping either.
            base, n = label, 2
            while label in results:
                label = f"{base}#{n}"
                n += 1
        x = domain.random(seed) if make_input is None else make_input()
        driver = make_driver(
            config,
            domain.mesh,
            domain.halo_spec,
            ndim=len(domain.global_interior),
            update_fn=update_fn,
        )
        results[label] = run_cycles(
            driver, x, n_cycles=n_cycles, repeats=repeats
        )
        driver.free()
    # divergence check, per pair: each comparison absorbs only the wire
    # tolerance of the two packers involved, so exact-vs-exact pairs keep
    # the tight historical 1e-3 guard even when lossy packers are swept.
    from repro.core.transport import get_packer

    def _wire_tol(res: CycleResult) -> tuple[float, float]:
        return get_packer(res.packer).wire_tolerance(domain.dtype)

    sums = {s: r.checksum for s, r in results.items()}
    ref_label, ref_res = next(iter(results.items()))
    ref = ref_res.checksum
    ref_rtol, ref_atol = _wire_tol(ref_res)
    for s, r in results.items():
        wr, wa = _wire_tol(r)
        rtol = max(1e-3, ref_rtol, wr)
        atol = max(1e-3, ref_atol, wa)
        assert abs(r.checksum - ref) < atol + rtol * abs(ref), (
            f"strategy {s} diverged from {ref_label}: {sums}"
        )
    return results


def speedup_vs_baseline(
    results: dict[str, CycleResult], baseline: str = "standard"
) -> dict[str, float]:
    """Per-strategy speedup multiplier vs the baseline (1.0 = parity)."""
    base = results[baseline].us_per_cycle
    return {s: base / r.us_per_cycle for s, r in results.items()}
