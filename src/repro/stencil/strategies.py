"""Pluggable exchange-strategy registry (Comb's comm-method table).

The paper benchmarks three MPI communication methods over one stencil
workload; Comb selects them by name on the command line.  This module is the
equivalent seam for the JAX port: every strategy is a registered
:class:`ExchangeStrategy` subclass selected through :func:`make_driver`, and
all strategy-specific knobs travel in a typed :class:`StrategyConfig` instead
of positional arguments threaded through the benchmark drivers.

Built-in strategies (the paper's three):

* ``standard``     — Alg. 1: per-iteration plan assembly + jit python
  dispatch (fresh Isend/Irecv envelopes each iteration).
* ``persistent``   — Alg. 2/3/4: AOT-compiled :class:`~repro.core.plan.
  CommPlan`, bare executable dispatch per iteration (``MPI_Start``).
* ``partitioned``  — Alg. 5/6/7: persistent lifecycle + every face split
  into ``n_parts`` partitions packed/sent/unpacked independently
  (``n_parts`` is the thread-count analogue of the paper's §VI sweep).

Adding a strategy::

    @register_strategy
    class MyStrategy(ExchangeStrategy):
        name = "mine"
        def init(self, example): ...
        def step(self, x): ...

and it is immediately sweepable by ``repro.stencil.sweep`` and selectable in
``comb_measure(strategies=("standard", "mine"))``.
"""

from __future__ import annotations

import abc
import dataclasses
import time
from typing import Callable, ClassVar

import jax
from jax.sharding import Mesh

from repro.core import compat
from repro.core.autotune import AUTO
from repro.core.halo import (
    HaloSpec,
    exchange,
    exchange_fused,
    fused_message_group,
    ghost_pspec,
    sequential_message_groups,
)
from repro.core.plan import (
    PLANS,
    CommPlan,
    PlanCache,
    transport_plan,
)
from repro.core.transport import (
    get_packer,
    get_transport,
    schedule_layouts,
    scheduled_collective_count,
)


# ---------------------------------------------------------------------------
# typed configuration
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class StrategyConfig:
    """Strategy-specific knobs, carried as one typed value.

    ``n_parts``      — partition count per face (partitioned only; the
                       thread-count analogue in the paper's §VI study).
    ``plan_cache``   — where persistent plans live: ``"private"`` (one fresh
                       plan per driver, freed with it), ``"shared"`` (the
                       process-wide :data:`~repro.core.plan.PLANS` table of
                       initialized requests), or an explicit
                       :class:`~repro.core.plan.PlanCache` instance.
    ``donate``       — donate the input buffer to the step executable
                       (in-place ghost update, the MPI buffer-reuse analogue).
    ``packer``       — registered :class:`~repro.core.transport.Packer` every
                       message of this strategy's exchange stages through
                       (``"slice"`` = inline lax staging, ``"pallas"`` = the
                       Comb-style copy kernel; a first-class §VI sweep axis).
    ``transport``    — registered :class:`~repro.core.transport.Transport`
                       backend moving the packed buffers (``"ppermute"``
                       in-process; ``"multihost"`` is the multi-process seam).
    ``coalesce``     — aggregate each delivery group's messages into ONE
                       contiguous wire buffer + one composed collective per
                       hop chain (static :class:`~repro.core.transport.
                       WireLayout` offset tables recorded in the persistent
                       plan; partitions stay pipelined rounds).  Default on;
                       the off-path is the uncoalesced baseline cell of the
                       §VI sweep's coalesce axis.
    ``mapping``      — registered process-to-node placement
                       (:mod:`repro.launch.mapping`) the driver's mesh was
                       built under.  Purely identity: the schedule never
                       depends on it, but it travels into
                       :class:`~repro.core.halo.HaloSpec` and the persistent
                       plan key, and the sweep/BENCH records stamp it per
                       cell.  Aliases (``"rb"``) canonicalize at
                       construction.

    ``name``, ``packer``, and ``coalesce`` also accept the sentinel
    ``"auto"``: :func:`make_driver` then routes to :class:`AutoStrategy`,
    which resolves every ``auto`` axis at plan-build time through
    :mod:`repro.core.autotune` (trace-driven cost model, else in-situ
    calibration).  A non-``auto`` value on any axis pins that axis and
    autotuning ranges only over the rest.
    """

    name: str = "standard"
    n_parts: int = 1
    plan_cache: str | PlanCache = "private"
    donate: bool = True
    packer: str = "slice"
    transport: str = "ppermute"
    coalesce: bool | str = True
    mapping: str = "row-major"
    #: membership epoch of the grid this driver's mesh belongs to
    #: (:mod:`repro.launch.membership`); ``None`` = outside the membership
    #: domain.  Identity only, like ``mapping``: it flows into
    #: :class:`~repro.core.halo.HaloSpec` and therefore every persistent
    #: plan key and ``ScheduleInfo.tag()``, so plans built before a
    #: JOIN/LOSS re-formation can never hit after it — and only
    #: epoch-stamped plans are candidates for
    #: :meth:`~repro.core.plan.PlanCache.invalidate_stale_epochs`.
    epoch: int | None = None

    def __post_init__(self):
        assert self.n_parts >= 1, self.n_parts
        if isinstance(self.plan_cache, str):
            assert self.plan_cache in ("private", "shared"), self.plan_cache
        if self.packer != AUTO:
            get_packer(self.packer)  # fail construction, not mid-sweep
        assert isinstance(self.coalesce, bool) or self.coalesce == AUTO, (
            self.coalesce
        )
        get_transport(self.transport)
        from repro.launch.mapping import canonical_mapping

        object.__setattr__(self, "mapping", canonical_mapping(self.mapping))

    def resolve_cache(self) -> PlanCache | None:
        """``None`` means un-cached private plans (freed by the driver)."""
        if isinstance(self.plan_cache, PlanCache):
            return self.plan_cache
        if self.plan_cache == "shared":
            return PLANS
        return None

    def with_(self, **kw) -> "StrategyConfig":
        return dataclasses.replace(self, **kw)


# ---------------------------------------------------------------------------
# strategy base class
# ---------------------------------------------------------------------------


class ExchangeStrategy(abc.ABC):
    """One halo-exchange (+ optional local update) iteration driver.

    Lifecycle mirrors the MPI request lifecycle the paper measures::

        drv.init(example)   # *_init   (no-op for the standard baseline)
        x = drv.step(x)     # Start / Isend+Irecv
        x = drv.wait(x)     # Waitall
        drv.free()          # Request_free
    """

    #: registry key; subclasses must override.
    name: ClassVar[str] = ""
    #: whether ``config.n_parts`` reaches the exchange (partitioned
    #: transport); non-partitioning strategies always exchange whole faces.
    uses_partitions: ClassVar[bool] = False
    #: whether ``init`` pays amortizable setup worth timing; benchmark
    #: harnesses charge ``init_us`` only to strategies that set this.
    amortizes_init: ClassVar[bool] = False

    def __init__(
        self,
        mesh: Mesh,
        spec_builder: Callable[[], HaloSpec],
        ndim: int,
        *,
        config: StrategyConfig | None = None,
        update_fn: Callable[[jax.Array], jax.Array] | None = None,
    ):
        self.mesh = mesh
        self.ndim = ndim
        self.config = (config or StrategyConfig(name=self.name)).with_(
            name=self.name
        )
        self._spec_builder = spec_builder
        self.update_fn = update_fn

    # -- identity ----------------------------------------------------------
    @property
    def strategy(self) -> str:
        return self.name

    @property
    def n_parts(self) -> int:
        return self.config.n_parts

    @property
    def packer(self) -> str:
        return self.config.packer

    @property
    def transport(self) -> str:
        return self.config.transport

    #: schedule identity recorded in compiled transport plans
    schedule_kind: ClassVar[str] = "sequential"

    def build_spec(self) -> HaloSpec:
        """The exchange plan inputs, stamped with this strategy's identity.

        Partition count, packer, and transport come from the *config*, not
        the builder — the builder only describes geometry (which axes, halo
        width, topology).  Strategies opt into partitioned transport via
        ``uses_partitions``.
        """
        spec = self._spec_builder()
        n_parts = self.n_parts if self.uses_partitions else 1
        return spec.with_(
            strategy=self.name, n_parts=n_parts,
            packer=self.config.packer, transport=self.config.transport,
            coalesce=self.config.coalesce, mapping=self.config.mapping,
            epoch=self.config.epoch,
        )

    # -- plan assembly ------------------------------------------------------
    def _build_step(self) -> Callable[[jax.Array], jax.Array]:
        spec = self.build_spec()  # neighbor tables, slabs, partitions
        pspec = ghost_pspec(spec, self.ndim)
        update = self.update_fn

        def step(x: jax.Array) -> jax.Array:
            x = exchange(x, spec)
            if update is not None:
                x = update(x)
            return x

        return compat.shard_map(
            step, mesh=self.mesh, in_specs=pspec, out_specs=pspec
        )

    # -- schedule introspection ---------------------------------------------
    def _local_block_shape(self, example_shape: tuple[int, ...]) -> tuple[int, ...]:
        """Per-shard ghosted block shape of a globally stored example."""
        spec = self.build_spec()
        shape = list(example_shape)
        for name, a in zip(spec.mesh_axes, spec.array_axes):
            shape[a] //= self.mesh.shape[name]
        return tuple(shape)

    def _message_groups(
        self, shape: tuple[int, ...], spec: HaloSpec
    ) -> tuple[tuple, ...]:
        """The strategy's message tables for one local block shape — the
        same assembler the traced step runs, evaluated outside the trace
        (axis sizes come from the mesh, not ``lax.axis_size``)."""
        sizes = {name: self.mesh.shape[name] for name in spec.mesh_axes}
        return sequential_message_groups(shape, spec, sizes)

    def scheduled_collectives(self, example: jax.Array) -> int:
        """Collectives one step launches — the §VI sweep records this next
        to the plan-cache counters so coalescing's one-collective-per-
        neighbor claim is visible in BENCH artifacts."""
        spec = self.build_spec()
        groups = self._message_groups(
            self._local_block_shape(example.shape), spec
        )
        return scheduled_collective_count(groups, coalesce=spec.coalesce)

    def replan_tables(self, example) -> tuple[tuple, tuple]:
        """Re-derive the FULL static transport schedule for the current
        topology: ``(message groups, wire layouts)``.

        This is the elastic re-plan primitive — after a mesh change the
        surviving topology's :class:`~repro.core.transport.Message` tables
        and :class:`~repro.core.transport.WireLayout` offset tables are
        recomputed from scratch.  The derivation is a pure function of
        (block shape, spec, mesh axis sizes): no device identity, rank id,
        or runtime state enters, so repeated calls — and calls on meshes
        with permuted devices — return identical tables (asserted by the
        elastic runner and tests/core/test_replan_purity.py).  Everything
        here is table math; the expensive trace+compile a topology change
        *also* triggers is measured separately as ``init_us``, while this
        call's time is the sweep's ``replan_us`` metric.
        """
        spec = self.build_spec()
        groups = self._message_groups(
            self._local_block_shape(tuple(example.shape)), spec
        )
        layouts = (
            schedule_layouts(groups, spec.packer, example.dtype)
            if spec.coalesce else ()
        )
        return groups, layouts

    def wire_layouts(self, example: jax.Array) -> tuple:
        """The coalesced schedule's static offset tables (empty when the
        strategy runs uncoalesced) — what persistent plans record."""
        return self.replan_tables(example)[1]

    # -- lifecycle ----------------------------------------------------------
    @abc.abstractmethod
    def init(self, example: jax.Array) -> None:
        """Pay any amortizable setup (trace+lower+compile for persistent)."""

    @abc.abstractmethod
    def step(self, x: jax.Array) -> jax.Array:
        """One exchange(+update) iteration; async (returns futures)."""

    @staticmethod
    def wait(x: jax.Array) -> jax.Array:
        return jax.block_until_ready(x)  # MPI_Waitall

    def free(self) -> None:
        """Release strategy-held executables (no-op by default)."""

    # -- introspection ------------------------------------------------------
    def compiled_text(self, example: jax.Array) -> str:
        """Post-optimization HLO of the step (for overlap/HLO analysis)."""
        raise NotImplementedError(f"{self.name} has no compiled plan")


# ---------------------------------------------------------------------------
# the registry
# ---------------------------------------------------------------------------

_REGISTRY: dict[str, type[ExchangeStrategy]] = {}


def register_strategy(cls: type[ExchangeStrategy]) -> type[ExchangeStrategy]:
    """Class decorator: add ``cls`` to the strategy table under ``cls.name``."""
    if not cls.name:
        raise ValueError(f"{cls.__name__} must set a non-empty `name`")
    if cls.name in _REGISTRY:
        raise ValueError(
            f"strategy {cls.name!r} already registered "
            f"({_REGISTRY[cls.name].__name__})"
        )
    _REGISTRY[cls.name] = cls
    return cls


def available_strategies() -> tuple[str, ...]:
    """Registered strategy names, registration order (paper order first)."""
    return tuple(_REGISTRY)


def get_strategy(name: str) -> type[ExchangeStrategy]:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown exchange strategy {name!r}; "
            f"registered: {', '.join(_REGISTRY) or '(none)'}"
        ) from None


def make_driver(
    strategy: str | StrategyConfig,
    mesh: Mesh,
    spec_builder: Callable[[], HaloSpec],
    ndim: int,
    *,
    update_fn: Callable[[jax.Array], jax.Array] | None = None,
    **config_kw,
) -> ExchangeStrategy:
    """The factory: name-or-config in, initialized-on-demand driver out.

    Any ``auto`` axis (name, packer, or coalesce) routes to
    :class:`AutoStrategy`, which resolves the remaining axes at plan-build
    time and then behaves exactly as the driver it picked.
    """
    if isinstance(strategy, StrategyConfig):
        config = strategy
    else:
        config = StrategyConfig(name=strategy, **config_kw)
    if AUTO in (config.name, config.packer, config.coalesce):
        return AutoStrategy(
            mesh, spec_builder, ndim, config=config, update_fn=update_fn
        )
    cls = get_strategy(config.name)
    return cls(mesh, spec_builder, ndim, config=config, update_fn=update_fn)


# ---------------------------------------------------------------------------
# the paper's three strategies
# ---------------------------------------------------------------------------


@register_strategy
class StandardStrategy(ExchangeStrategy):
    """Alg. 1: plan re-assembled in python + jit-dispatch every iteration.

    The compiled executable is reused (as MPI reuses connection state) —
    only the per-iteration envelope/plan assembly differs from persistent.
    """

    name = "standard"

    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        self._jitted = None  # compiled state reused across iterations

    def init(self, example: jax.Array) -> None:
        return None  # nothing to amortize: baseline sets up per iteration

    def step(self, x: jax.Array) -> jax.Array:
        # Re-derive the plan in python every iteration (neighbor tables,
        # slab geometry, partition layout) — the envelope-posting work
        # persistent MPI amortizes — then dispatch via the jit python path.
        spec = self.build_spec()
        for name in spec.mesh_axes:  # envelope assembly per neighbor pair
            k = self.mesh.shape[name]
            _ = [(i, (i - 1) % k) for i in range(k)]
            _ = [(i, (i + 1) % k) for i in range(k)]
        return self._jit()(x)

    def _jit(self):
        if self._jitted is None:
            donate = (0,) if self.config.donate else ()
            self._jitted = jax.jit(self._build_step(), donate_argnums=donate)
        return self._jitted

    def free(self) -> None:
        self._jitted = None

    def compiled_text(self, example) -> str:
        return self._jit().lower(example).compile().as_text()


@register_strategy
class PersistentStrategy(ExchangeStrategy):
    """Alg. 2/3/4: AOT-compile once at ``init``, bare dispatch per ``step``."""

    name = "persistent"
    amortizes_init = True

    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        self._plan: CommPlan | None = None

    def _plan_key(self, example: jax.Array):
        """Structural plan identity: the step fn is a fresh closure per
        driver, so the cache key must come from what the closure *computes*
        — spec geometry, mesh, update fn, and the abstract input.  The mesh
        and update fn go in by *object* (the cache holds them alive, so
        their identity can't be recycled), letting equal meshes share."""
        return (
            "halo_plan", self.build_spec(), self.ndim, self.config.donate,
            self.mesh, self.update_fn,
            example.shape, str(example.dtype), str(example.sharding),
        )

    def _make_plan(
        self, example: jax.Array, example_args, donate: tuple[int, ...]
    ) -> CommPlan:
        """Overridable plan assembly; ``init`` computes the inputs once.

        The compiled executable is a *transport schedule*: its identity
        (plan name + structural cache key via :meth:`_plan_key` -> spec)
        records the choreography kind, the packer/transport backends, and
        the coalesce mode; a coalesced plan also records its static wire-
        buffer offset tables (``plan.wire_layouts``), computed here exactly
        once — the ``MPI_Send_init`` buffer-amortization analogue.
        """
        return transport_plan(
            self._build_step, example_args,
            schedule=self.build_spec().schedule_info(self.schedule_kind),
            layouts=lambda: self.wire_layouts(example),
            donate_argnums=donate,
            cache=self.config.resolve_cache(), key=self._plan_key(example),
            name=f"halo_{self.name}@{self.config.packer}",
        )

    def init(self, example: jax.Array) -> None:
        if self._plan is not None:
            return
        donate = (0,) if self.config.donate else ()
        example_args = (
            jax.ShapeDtypeStruct(
                example.shape, example.dtype, sharding=example.sharding
            ),
        )
        self._plan = self._make_plan(example, example_args, donate)

    def step(self, x: jax.Array) -> jax.Array:
        if self._plan is None:
            self.init(x)
        # MPI_Startall: bare dispatch of the AOT-compiled executable —
        # async, zero plan assembly, no jit python path in front.
        return self._plan.start(x)

    def free(self) -> None:
        # shared-cache plans stay initialized for other drivers (freed via
        # the cache's own free_all), private plans die with the driver.
        if self._plan is not None and self.config.resolve_cache() is None:
            self._plan.free()
        self._plan = None

    def compiled_text(self, example: jax.Array) -> str:
        if self._plan is None:
            self.init(example)
        assert self._plan is not None
        return self._plan.as_text()


@register_strategy
class PartitionedStrategy(PersistentStrategy):
    """Alg. 5/6/7: persistent lifecycle, faces split into ``n_parts``
    partitions each packed -> sent -> unpacked independently (early work)."""

    name = "partitioned"
    uses_partitions = True


# ---------------------------------------------------------------------------
# overlap strategies (beyond the paper's trio)
# ---------------------------------------------------------------------------


@register_strategy
class FusedStrategy(PersistentStrategy):
    """Fused multi-axis exchange: all D axis passes in one combined step.

    The sequential schedule exchanges axis by axis (each pass's slabs
    include the previous pass's refreshed ghosts, the corner trick); the
    fused schedule posts all ``3^D - 1`` face/edge/corner messages from the
    original buffer in a single pass (:func:`repro.core.halo.
    exchange_fused`) and compiles them into ONE multi-axis
    :class:`~repro.core.plan.CommPlan` (a ``"fused"``-kind transport
    schedule via :func:`repro.core.plan.transport_plan`).  No message
    depends on another, so packs, sends, and unpacks of every axis may
    overlap — trading D dependent passes for maximal concurrency, the Comb
    fused-packing analogue.
    """

    name = "fused"
    schedule_kind = "fused"

    def _message_groups(self, shape, spec):
        sizes = {name: self.mesh.shape[name] for name in spec.mesh_axes}
        return (fused_message_group(shape, spec, sizes),)

    def _build_step(self) -> Callable[[jax.Array], jax.Array]:
        spec = self.build_spec()
        pspec = ghost_pspec(spec, self.ndim)
        update = self.update_fn

        def step(x: jax.Array) -> jax.Array:
            x = exchange_fused(x, spec)
            if update is not None:
                x = update(x)
            return x

        return compat.shard_map(
            step, mesh=self.mesh, in_specs=pspec, out_specs=pspec
        )


@register_strategy
class OverlapStrategy(PersistentStrategy):
    """Double-buffered ghosts: interior update overlapped with the exchange.

    The classic communication/computation-overlap schedule: each step reads
    buffer A and writes buffer B (donation is disabled so both stay live —
    the double buffer; the returned buffer feeds the next step, so the pair
    alternates).  The local update is split by :func:`repro.stencil.domain.
    interior_halo_split`: the deep-interior piece is computed from buffer A
    *while* the boundary exchange is in flight (it has no data dependency
    on the collectives), and only the thin boundary shells wait for the
    refreshed ghosts.

    ``update_fn`` must satisfy the split contract (local shift-invariant
    stencil of radius <= halo on decomposed axes, rim left untouched);
    without an ``update_fn`` the step degenerates to a persistent exchange.
    """

    name = "overlap"

    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        # double buffering is the whole point: never update in place.
        self.config = self.config.with_(donate=False)

    def _build_step(self) -> Callable[[jax.Array], jax.Array]:
        from repro.stencil.domain import overlapped_update

        spec = self.build_spec()
        pspec = ghost_pspec(spec, self.ndim)
        update = self.update_fn

        def step(x: jax.Array) -> jax.Array:
            fresh = exchange(x, spec)  # boundary exchange in flight...
            if update is None:
                return fresh
            # ...while the deep interior computes from the stale buffer
            return overlapped_update(
                x, fresh, update,
                array_axes=spec.array_axes, halo=spec.halo,
            )

        return compat.shard_map(
            step, mesh=self.mesh, in_specs=pspec, out_specs=pspec
        )


# ---------------------------------------------------------------------------
# autotuned selection (not registered: "auto" is a selector, not a schedule)
# ---------------------------------------------------------------------------


class AutoStrategy(ExchangeStrategy):
    """Resolve every ``auto`` config axis at plan-build time, then delegate.

    On the first ``init``/``step`` the driver enumerates the candidate
    ``(strategy, packer, coalesce, n_parts)`` grid (any concretely-pinned
    axis stays pinned), computes each candidate's static schedule features
    — ``wire_bytes``, collective count, and the intra/inter-node send tally
    under the LIVE mesh's node vector — and asks the process-wide
    :func:`repro.core.autotune.default_tuner` to pick: by recorded trace,
    by fitted cost model, or (when neither covers the cell) by in-situ
    timed probes through this driver's own plan cache.  The winning probe's
    compiled plan is thereby already initialized when the resolved inner
    driver starts — the paper's amortization argument applied to the tuning
    step itself.

    After resolution the driver IS the chosen one: ``strategy``/``config``
    report the concrete cell, and ``selected_by``/``predicted_us``/
    ``calibration_us`` carry the provenance that
    :func:`repro.stencil.comb.run_cycles` stamps into BENCH records.
    ``selected_by`` also lands in :class:`~repro.core.halo.HaloSpec` (and
    so in every persistent plan key): an autotuned plan never silently
    aliases a hand-pinned one.
    """

    name = AUTO
    amortizes_init = True  # resolution + the inner init are the setup cost

    def __init__(self, mesh, spec_builder, ndim, *, config=None,
                 update_fn=None):
        config = config or StrategyConfig(
            name=AUTO, packer=AUTO, coalesce=AUTO
        )
        super().__init__(
            mesh, spec_builder, ndim, config=config, update_fn=update_fn
        )
        # the base ctor stamps name="auto"; restore the caller's strategy
        # pin (e.g. name="persistent", packer="auto" tunes the packer only)
        self.config = config
        self._inner: ExchangeStrategy | None = None
        self._owned_cache: PlanCache | None = None
        #: selection provenance, populated at resolution
        self.selected_by: str | None = None
        self.predicted_us: float | None = None
        self.calibration_us: float = 0.0

    # -- identity: the sentinel before resolution, the winner after --------
    @property
    def strategy(self) -> str:
        return self._inner.strategy if self._inner is not None else AUTO

    @property
    def n_parts(self) -> int:
        return self._inner.n_parts if self._inner is not None else 1

    # -- candidate grid -----------------------------------------------------
    def _probe_plan_cache(self) -> str | PlanCache:
        """Probe drivers and the resolved driver share ONE cache, so the
        winner's probe plan is a cache hit, not a recompile.  A "private"
        request becomes a driver-owned cache (freed with this driver);
        "shared"/explicit caches pass through."""
        if self.config.plan_cache == "private":
            if self._owned_cache is None:
                self._owned_cache = PlanCache()
            return self._owned_cache
        return self.config.plan_cache

    def _candidate_config(self, cand) -> StrategyConfig:
        return self.config.with_(
            name=cand.strategy, packer=cand.packer,
            coalesce=cand.coalesce, n_parts=cand.n_parts,
            plan_cache=self._probe_plan_cache(),
        )

    def _candidates(self, dtype):
        from repro.core import autotune

        pin = lambda v: None if v == AUTO else (v,)
        return autotune.default_candidates(
            dtype=dtype,
            strategies=pin(self.config.name),
            packers=pin(self.config.packer),
            coalesce_modes=(
                None if self.config.coalesce == AUTO
                else (bool(self.config.coalesce),)
            ),
            part_counts=(
                autotune.DEFAULT_PART_COUNTS if self.config.n_parts == 1
                else (self.config.n_parts,)
            ),
        )

    # -- resolution ---------------------------------------------------------
    def _probe(self, cand, example: jax.Array) -> float:
        """One timed calibration run of a candidate (Comb protocol in
        miniature: init, warmup, barrier, timed cycles).  Probes run on a
        COPY of the example (donation-safe, and legal on non-addressable
        multihost arrays, unlike ``jnp.array``), through a plan spec
        stamped ``selected_by="calibration"`` — the same stamp the resolved
        driver uses, so the winner's plan key matches and its compiled plan
        is reused."""
        from repro.core.autotune import PROBE_CYCLES, PROBE_WARMUP

        drv = make_driver(
            self._candidate_config(cand), self.mesh,
            lambda: self._spec_builder().with_(selected_by="calibration"),
            self.ndim, update_fn=self.update_fn,
        )
        x = jax.jit(lambda a: a + 0)(example)
        try:
            drv.init(x)
            for _ in range(PROBE_WARMUP):
                x = drv.step(x)
            drv.wait(x)
            t0 = time.perf_counter()
            for _ in range(PROBE_CYCLES):
                x = drv.step(x)
            drv.wait(x)
            us = (time.perf_counter() - t0) / PROBE_CYCLES * 1e6
            if jax.process_count() > 1:
                # every rank must adopt the SAME timing or the SPMD ranks
                # could resolve different winners and deadlock the mesh
                from jax.experimental import multihost_utils
                import numpy as np

                us = float(multihost_utils.broadcast_one_to_all(
                    np.float32(us)
                ))
            return us
        finally:
            drv.free()  # the shared probe cache keeps the plan initialized

    def _resolve(self, example) -> None:
        if self._inner is not None:
            return
        import numpy as np

        from repro.core import autotune
        from repro.core.transport import schedule_locality
        from repro.launch.mapping import default_node_size, mesh_node_ids

        geo = self._spec_builder()  # geometry only: axes, halo, topology
        candidates = self._candidates(example.dtype)
        axis_names = tuple(self.mesh.axis_names)
        axis_sizes = {name: self.mesh.shape[name] for name in axis_names}
        n_devices = int(self.mesh.devices.size)
        node_size = default_node_size(n_devices, jax.process_count())
        node_of = mesh_node_ids(self.mesh, node_size)
        # per-shard ghosted block shape (pure geometry, no strategy id)
        block = list(example.shape)
        for name, a in zip(geo.mesh_axes, geo.array_axes):
            block[a] //= self.mesh.shape[name]
        face_elems = autotune.max_face_elems(
            tuple(block), geo.array_axes, geo.halo
        )
        cell = {
            "mesh_shape": tuple(axis_sizes[name] for name in axis_names),
            "shape": tuple(example.shape),
            "dtype": str(example.dtype),
            "halo": geo.halo,
            "mapping": self.config.mapping,
            "transport": self.config.transport,
            "node_size": node_size,
            "message_bytes": face_elems * np.dtype(example.dtype).itemsize,
        }
        # static features per candidate; message tables depend only on
        # (strategy, n_parts) — packer/coalesce reuse them (same rule as
        # the sweep's groups_cache)
        groups_cache: dict[tuple[str, int], tuple] = {}
        features = {}
        for cand in candidates:
            gkey = (cand.strategy, cand.n_parts)
            if gkey not in groups_cache:
                drv = make_driver(
                    self._candidate_config(cand), self.mesh,
                    self._spec_builder, self.ndim, update_fn=self.update_fn,
                )
                groups_cache[gkey] = drv._message_groups(
                    drv._local_block_shape(tuple(example.shape)),
                    drv.build_spec(),
                )
            groups = groups_cache[gkey]
            loc = schedule_locality(
                groups, axis_order=axis_names, axis_sizes=axis_sizes,
                node_of=node_of,
            )
            features[cand] = autotune.CellFeatures(
                wire_bytes=face_elems
                * get_packer(cand.packer).wire_itemsize(example.dtype),
                collective_count=scheduled_collective_count(
                    groups, coalesce=cand.coalesce
                ),
                intra_sends=loc.intra_sends,
                inter_sends=loc.inter_sends,
            )
        verdict = autotune.default_tuner().choose_or_calibrate(
            candidates, features, cell,
            probe=lambda cand: self._probe(cand, example),
        )
        self.selected_by = verdict.selected_by
        self.predicted_us = verdict.predicted_us
        self.calibration_us = verdict.calibration_us
        stamp = verdict.plan_stamp()
        self._inner = make_driver(
            self._candidate_config(verdict.candidate), self.mesh,
            lambda: self._spec_builder().with_(selected_by=stamp),
            self.ndim, update_fn=self.update_fn,
        )
        # the resolved driver's config (incl. overlap's forced
        # donate=False) becomes this driver's visible identity
        self.config = self._inner.config

    # -- lifecycle: resolve, then delegate ----------------------------------
    def init(self, example: jax.Array) -> None:
        self._resolve(example)
        self._inner.init(example)

    def step(self, x: jax.Array) -> jax.Array:
        if self._inner is None:
            self._resolve(x)
        return self._inner.step(x)

    def free(self) -> None:
        if self._inner is not None:
            self._inner.free()
        if self._owned_cache is not None:
            self._owned_cache.free_all()

    def build_spec(self) -> HaloSpec:
        if self._inner is None:
            raise RuntimeError(
                "auto strategy has no spec before resolution; "
                "call init(example) first"
            )
        return self._inner.build_spec()

    def scheduled_collectives(self, example: jax.Array) -> int:
        self._resolve(example)
        return self._inner.scheduled_collectives(example)

    def replan_tables(self, example) -> tuple[tuple, tuple]:
        self._resolve(example)
        return self._inner.replan_tables(example)

    def wire_layouts(self, example: jax.Array) -> tuple:
        self._resolve(example)
        return self._inner.wire_layouts(example)

    def compiled_text(self, example: jax.Array) -> str:
        self._resolve(example)
        return self._inner.compiled_text(example)
