"""Persistent communication/step plans — the MPI persistent-request analogue.

The paper's persistent MPI (`MPI_Send_init` / `MPI_Start` / `MPI_Wait` /
`MPI_Request_free`) amortizes per-message setup over all iterations of an
iterative exchange.  The XLA-native analogue implemented here:

* **init**  -> trace + lower + compile the SPMD step once (``jax.jit(...).
  lower(...).compile()``); permutation tables and block slices are baked in as
  static constants (the "tag-matching done at init" analogue).
* **start** -> dispatch the pre-compiled executable (async under JAX's
  dispatch model — the returned arrays are futures).
* **wait**  -> ``jax.block_until_ready`` on the outputs.
* **free**  -> drop the executable.

A process-wide :class:`PlanCache` plays the role of the application's table of
initialized persistent requests; its hit/miss counters let tests and
benchmarks measure the amortization the paper reports (setup paid once).

The *standard* (non-persistent) baseline is modeled by :func:`dispatch_standard`,
which re-derives the plan arguments and goes through the normal ``jax.jit``
python dispatch path every call — preserving the relative per-iteration
overhead the paper measures between baseline and persistent modes.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any, Callable, Hashable, Sequence

import jax
import numpy as np

from repro.core import tracing


def _abstractify(x: Any) -> Any:
    """Concrete array / ShapeDtypeStruct -> hashable abstract description."""
    if isinstance(x, jax.ShapeDtypeStruct):
        return (x.shape, str(x.dtype), str(getattr(x, "sharding", None)))
    if isinstance(x, (jax.Array, np.ndarray)):
        sh = getattr(x, "sharding", None)
        return (x.shape, str(x.dtype), str(sh))
    return ("static", repr(x))


@dataclasses.dataclass
class PlanStats:
    inits: int = 0
    starts: int = 0
    cache_hits: int = 0
    init_seconds: float = 0.0
    frees: int = 0
    #: plans dropped because their topology died under them (elastic
    #: re-meshing); the next get_or_init on the new mesh pays a fresh init
    invalidations: int = 0


class CommPlan:
    """One persistent plan: a pre-compiled SPMD step with a fixed signature.

    Mirrors the MPI persistent-request lifecycle::

        plan = CommPlan(fn, example_args=...)     # MPI_Send_init
        out  = plan.start(*args)                  # MPI_Start(all)
        out  = plan.wait(out)                     # MPI_Wait(all)
        plan.free()                               # MPI_Request_free
    """

    def __init__(
        self,
        fn: Callable,
        *,
        example_args: Sequence[Any],
        mesh: jax.sharding.Mesh | None = None,
        in_shardings: Any = None,
        out_shardings: Any = None,
        donate_argnums: tuple[int, ...] = (),
        static_argnums: tuple[int, ...] = (),
        name: str | None = None,
    ):
        self.name = name or getattr(fn, "__name__", "plan")
        #: transport-schedule identity + coalesced wire-layout offset
        #: tables, stamped by :func:`transport_plan` at init
        self.schedule = None
        self.wire_layouts: tuple = ()
        self._freed = False
        #: dispatches of the compiled executable, and the host seconds
        #: spent in them (the ``plan.start`` span, traced or not)
        self.starts = 0
        self.start_seconds = 0.0
        t0 = time.perf_counter()
        kw: dict[str, Any] = dict(
            donate_argnums=donate_argnums, static_argnums=static_argnums
        )
        if in_shardings is not None:
            kw["in_shardings"] = in_shardings
        if out_shardings is not None:
            kw["out_shardings"] = out_shardings
        jitted = jax.jit(fn, **kw)
        ctx = mesh if mesh is not None else _NullCtx()
        with tracing.span(tracing.PLAN_BUILD), ctx:  # type: ignore[attr-defined]
            self.lowered = jitted.lower(*example_args)
            self.compiled = self.lowered.compile()
        self.init_seconds = time.perf_counter() - t0

    # -- lifecycle ---------------------------------------------------------
    def start(self, *args: Any) -> Any:
        """Begin the exchange (async dispatch of the compiled executable)."""
        if self._freed:
            raise RuntimeError(f"plan {self.name!r} used after free()")
        t0 = time.perf_counter()
        with tracing.span(tracing.PLAN_START):
            out = self.compiled(*args)
        self.start_seconds += time.perf_counter() - t0
        self.starts += 1
        return out

    @staticmethod
    def wait(out: Any) -> Any:
        """Block until the started exchange has completed."""
        return jax.block_until_ready(out)

    def __call__(self, *args: Any) -> Any:
        return self.start(*args)

    def free(self) -> None:
        self._freed = True
        self.compiled = None
        self.lowered = None

    # -- introspection (feeds the dry-run / roofline) -----------------------
    def memory_analysis(self) -> Any:
        return self.compiled.memory_analysis()

    def cost_analysis(self) -> dict:
        from repro.core.compat import cost_analysis_dict

        return cost_analysis_dict(self.compiled)

    def as_text(self) -> str:
        return self.compiled.as_text()

    def lowered_text(self) -> str:
        return self.lowered.as_text()


class _NullCtx:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


def _starts(plan: Any) -> int:
    """A plan's own count of starts; a plan-like stand-in counts none."""
    return getattr(plan, "starts", 0)


class PlanCache:
    """Registry of initialized persistent plans (keyed by fn + abstract args).

    The framework-wide instance (:data:`PLANS`) is what the training loop and
    serving engine use; per-instance caches can be created for tests.
    """

    def __init__(self) -> None:
        self._plans: dict[Hashable, CommPlan] = {}
        self._lock = threading.Lock()
        self._stats = PlanStats()
        self._freed_starts = 0  # starts of the plans this cache has freed

    @property
    def stats(self) -> PlanStats:
        """The cache's counters; ``starts`` sums the starts of every plan
        it has held, as each plan counts them itself."""
        with self._lock:
            self._stats.starts = self._freed_starts + sum(
                _starts(p) for p in self._plans.values())
        return self._stats

    def _free(self, plan: CommPlan) -> None:
        self._freed_starts += _starts(plan)
        plan.free()
        self._stats.frees += 1

    def key_for(self, fn: Callable, args: Sequence[Any], extra: Hashable = ()) -> Hashable:
        flat, treedef = jax.tree.flatten(list(args))
        return (
            getattr(fn, "__qualname__", repr(fn)),
            id(getattr(fn, "__wrapped__", fn)),
            str(treedef),
            tuple(_abstractify(x) for x in flat),
            extra,
        )

    def get_or_init(
        self,
        fn: Callable,
        args: Sequence[Any],
        *,
        extra_key: Hashable = (),
        key: Hashable | None = None,
        lazy_fn: bool = False,
        **plan_kwargs: Any,
    ) -> CommPlan:
        """``key`` overrides the default (qualname + fn identity + abstract
        args) cache key entirely — for callers whose ``fn`` is a fresh
        closure each time (e.g. exchange strategies rebuilding their step)
        but whose plan identity is structural.  With ``lazy_fn``, ``fn`` is
        a zero-arg *factory* for the real function, only invoked on a miss
        (a hit skips plan assembly entirely, as MPI_Start skips setup)."""
        if key is None:
            assert not lazy_fn, "lazy_fn requires an explicit structural key"
            key = self.key_for(fn, args, extra_key)
        with self._lock:
            plan = self._plans.get(key)
            if plan is not None:
                self._stats.cache_hits += 1
                return plan
        plan = CommPlan(fn() if lazy_fn else fn, example_args=args,
                        **plan_kwargs)
        with self._lock:
            self._plans[key] = plan
            self._stats.inits += 1
            self._stats.init_seconds += plan.init_seconds
        return plan

    def invalidate(self, predicate: Callable[[Hashable], bool] | None = None) -> int:
        """Drop (and free) cached plans whose topology no longer exists.

        This is the elastic re-mesh path: after rank loss the surviving
        processes re-form the mesh, and every plan compiled against the old
        device assignment is garbage — its permutation tables name shards
        that are gone.  ``predicate`` selects which keys to drop (default:
        all).  Returns the number of invalidated plans; the count is also
        accumulated in ``stats.invalidations`` (a BENCH-recorded metric).

        A plan build *in flight* during the failure never lands here:
        :meth:`get_or_init` inserts only after a successful init, so an
        aborted build cannot poison the cache.
        """
        with self._lock:
            doomed = [k for k in self._plans
                      if predicate is None or predicate(k)]
            for k in doomed:
                self._free(self._plans.pop(k))
            self._stats.invalidations += len(doomed)
        return len(doomed)

    def free_all(self) -> None:
        with self._lock:
            for p in self._plans.values():
                self._free(p)
            self._plans.clear()

    def invalidate_stale_epochs(self, live_epoch: int) -> int:
        """Drop only the plans compiled against a dead membership epoch.

        The in-grid recovery path (:mod:`repro.launch.membership`): when the
        coordinator bumps the grid to ``live_epoch`` after a JOIN or rank
        loss, plans stamped with an older epoch can never deliver into the
        re-formed mesh — but everything else a surviving rank has warmed up
        (other shapes, other workloads, epoch-free plans) stays resident.
        That retention is the whole point of recovering without a relaunch.
        """
        return self.invalidate(lambda key: stale_epoch(key, live_epoch))

    def keys(self) -> tuple:
        """Snapshot of the resident plan keys (retention assertions: the
        in-grid chaos tests prove unrelated entries survive a recovery)."""
        with self._lock:
            return tuple(self._plans)

    def __len__(self) -> int:
        return len(self._plans)


def stale_epoch(key: Hashable, live_epoch: int) -> bool:
    """True when any element of a (possibly nested) plan key carries a
    membership ``epoch`` older than ``live_epoch``.

    Plan keys are structural tuples; the epoch rides inside whatever spec
    object the strategy embeds (e.g. :class:`~repro.core.halo.HaloSpec`),
    so this walks the key duck-typed rather than binding to one spec type.
    Keys with no epoch-stamped element (``epoch`` absent or ``None``) are
    never stale — epoch-free callers (the whole non-elastic world) are
    untouched by epoch invalidation.
    """
    def walk(obj) -> bool:
        epoch = getattr(obj, "epoch", None)
        if isinstance(epoch, int) and not isinstance(epoch, bool) \
                and epoch < live_epoch:
            return True
        if isinstance(obj, tuple):
            return any(walk(el) for el in obj)
        return False

    return walk(key)


#: process-wide persistent-plan registry
PLANS = PlanCache()


def build_plan(
    step_factory: Callable[[], Callable],
    example_args: Sequence[Any],
    *,
    donate_argnums: tuple[int, ...] = (),
    cache: "PlanCache | None" = None,
    key: Hashable | None = None,
    name: str | None = None,
) -> CommPlan:
    """Assemble one persistent plan, private or from a shared cache.

    The cache-vs-private branch of every persistent-style ``init`` lives
    here exactly once.  Without ``cache`` the factory is invoked and the
    plan is owned by the caller; with ``cache`` the plan joins that table
    of initialized requests under ``key`` (which must then be a structural
    key, as for :meth:`PlanCache.get_or_init`) and the factory only runs
    on a miss — the step is NOT rebuilt or recompiled on a hit.
    """
    if cache is None:
        return CommPlan(
            step_factory(),  # plan assembled exactly once
            example_args=example_args, donate_argnums=donate_argnums,
            name=name,
        )
    assert key is not None, "cached plans need a structural key"
    return cache.get_or_init(
        step_factory, example_args, key=key,
        donate_argnums=donate_argnums, name=name, lazy_fn=True,
    )


def transport_plan(
    step_factory: Callable[[], Callable],
    example_args: Sequence[Any],
    *,
    schedule: Any,
    layouts: Sequence[Any] | Callable[[], Sequence[Any]] | None = None,
    donate_argnums: tuple[int, ...] = (),
    cache: "PlanCache | None" = None,
    key: Hashable | None = None,
    name: str | None = None,
) -> CommPlan:
    """Compile ONE persistent plan for a transport schedule.

    ``schedule`` is a :class:`repro.core.transport.ScheduleInfo` naming the
    choreography (sequential/fused), the mesh axes it spans, the registered
    packer/transport backends every message resolves, and whether messages
    coalesce — so the compiled executable's identity (plan name, and the
    structural cache ``key`` the caller derives from its spec) always
    records *which* pack/transport pipeline was baked in.  ``layouts`` is
    the coalesced schedule's static :class:`~repro.core.transport.
    WireLayout` offset tables (one per wire buffer) — a sequence, or a
    zero-arg factory invoked only when the plan is freshly stamped —
    recorded on the plan (``plan.wire_layouts``) as introspection the way
    ``MPI_Send_init`` records its amortized buffers: computed once at the
    plan's first init, never per ``start`` and never again on a cache hit.
    This is the one place the free-floating "compile this exchange step"
    call used to live; every persistent-style strategy now initializes
    through it.
    """
    axes = tuple(schedule.mesh_axes)
    assert axes, "a transport plan needs at least one mesh axis"
    assert len(set(axes)) == len(axes), f"duplicate mesh axes: {axes}"
    plan = build_plan(
        step_factory, example_args, donate_argnums=donate_argnums,
        cache=cache, key=key, name=name or schedule.tag(),
    )
    if plan.schedule is None:  # a cache hit keeps its original stamp
        plan.schedule = schedule
        if callable(layouts):
            layouts = layouts()
        plan.wire_layouts = tuple(layouts) if layouts is not None else ()
    return plan


def multi_axis_plan(
    step_factory: Callable[[], Callable],
    example_args: Sequence[Any],
    *,
    mesh_axes: Sequence[str],
    packer: str = "slice",
    transport: str = "ppermute",
    coalesce: bool = False,
    mapping: str = "row-major",
    layouts: Sequence[Any] | None = None,
    donate_argnums: tuple[int, ...] = (),
    cache: "PlanCache | None" = None,
    key: Hashable | None = None,
    name: str | None = None,
) -> CommPlan:
    """Build ONE persistent plan spanning every mesh axis of an exchange.

    The sequential schedule would compile (or at least sequence) one
    exchange pass per decomposed mesh axis; the fused multi-axis schedule
    hands the whole D-axis step to a single :class:`CommPlan` so every
    pack/send/unpack of every axis lives in one AOT-compiled executable —
    the ``MPI_Send_init`` of all ``3^D - 1`` neighbor requests at once.
    Assembly delegates to :func:`transport_plan` with a ``"fused"``
    schedule identity.
    """
    from repro.core.transport import ScheduleInfo

    return transport_plan(
        step_factory, example_args,
        schedule=ScheduleInfo(
            kind="fused", mesh_axes=tuple(mesh_axes),
            packer=packer, transport=transport, coalesce=coalesce,
            mapping=mapping,
        ),
        layouts=layouts,
        donate_argnums=donate_argnums, cache=cache, key=key, name=name,
    )


def persistent(
    fn: Callable | None = None,
    *,
    cache: PlanCache | None = None,
    donate_argnums: tuple[int, ...] = (),
    mesh: jax.sharding.Mesh | None = None,
) -> Callable:
    """Decorator: make ``fn`` execute through a persistent plan.

    First call with a given abstract signature pays init (trace+compile);
    subsequent calls dispatch the stored executable directly.  This is the
    ergonomic form used by the training loop and serving engine.
    """

    def deco(f: Callable) -> Callable:
        c = cache if cache is not None else PLANS

        def wrapper(*args: Any) -> Any:
            plan = c.get_or_init(
                f, args, donate_argnums=donate_argnums, mesh=mesh
            )
            return plan.start(*args)

        wrapper.__wrapped__ = f  # type: ignore[attr-defined]
        wrapper.__name__ = getattr(f, "__name__", "persistent_fn")  # type: ignore
        wrapper.plan_cache = c  # type: ignore[attr-defined]
        return wrapper

    return deco(fn) if fn is not None else deco


def dispatch_standard(fn: Callable, *args: Any, **jit_kwargs: Any) -> Any:
    """The *baseline* (non-persistent) dispatch path.

    Re-wraps ``fn`` in a fresh ``jax.jit`` object each call, so python-level
    plan assembly (signature hashing, sharding resolution, dispatch-cache
    lookup) is re-done per iteration — the analogue of posting fresh
    ``MPI_Isend``/``Irecv`` envelopes each iteration.  XLA's compile cache
    still avoids recompiling the HLO (as MPI avoids re-opening connections),
    so the measured difference is exactly the per-iteration setup the paper's
    persistent mode amortizes.
    """
    return jax.jit(fn, **jit_kwargs)(*args)
