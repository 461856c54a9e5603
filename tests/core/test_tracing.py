"""The exchange's layers named inside the program: every op of a compiled
step carries the scope of its layer, and the persistent plan counts its
own starts."""

import re

import jax
import numpy as np
import pytest

from repro.core import tracing
from repro.core.compat import make_mesh
from repro.core.plan import CommPlan, PlanCache
from repro.stencil import Domain, StrategyConfig, make_driver, stencil27_update

INSTRUCTION = re.compile(r"\s*(?:ROOT\s+)?%(\S+)\s*=\s*\S+\s+([a-z-]+)\(")
OP_NAME = re.compile(r'op_name="([^"]*)"')
COLLECTIVE = re.compile(r"collective-permute|all-reduce|all-gather")


def _scoped_ops(text: str) -> list[tuple[str, str, list[str]]]:
    """(name, opcode, layer scopes) of each instruction the program traced:
    those with an ``op_name`` below the step, parameters' aside."""
    out = []
    for line in text.splitlines():
        head, name = INSTRUCTION.match(line), OP_NAME.search(line)
        if not head or not name or "/" not in name.group(1):
            continue
        parts = name.group(1).split("/")
        if head.group(2) == "constant" and parts[-1] == "shard_map":
            continue  # a constant hoisted out of every scope
        out.append((head.group(1), head.group(2),
                    [p for p in parts if p in tracing.SCOPES]))
    return out


@pytest.fixture(scope="module", params=[1, 4], ids=["1dev", "4dev"])
def step_hlo(request):
    """A coalesced, partitioned exchange and the 27-point update at a tiny
    block, compiled on 1 or on 4 (2x2) virtual devices."""
    n = request.param
    shape = (2, 2) if n == 4 else (1, 1)
    mesh = make_mesh(shape, ("pz", "py"), devices=jax.devices()[:n])
    dom = Domain(mesh, global_interior=(8 * shape[0], 8 * shape[1], 16),
                 mesh_axes=("pz", "py", None))
    update = stencil27_update(np.full((3, 3, 3), 1 / 27, np.float32),
                              impl="xla")
    drv = make_driver(StrategyConfig(name="partitioned", n_parts=2,
                                     coalesce=True),
                      dom.mesh, dom.halo_spec, ndim=3, update_fn=update)
    x = dom.random(0)
    drv.init(x)
    yield n, drv.compiled_text(x)
    drv.free()


def test_every_op_of_the_step_carries_its_layer(step_hlo):
    n, text = step_hlo
    ops = _scoped_ops(text)
    assert ops
    seen = set()
    for name, opcode, scopes in ops:
        # the step is the exchange then the update, nothing else
        assert scopes and scopes[0] in (tracing.EXCHANGE, tracing.UPDATE), (
            name, scopes)
        if scopes[0] == tracing.EXCHANGE:
            # inside the exchange every op is a packer's or the transport's
            assert scopes[1:] and scopes[-1] in (
                tracing.PACK, tracing.PERMUTE, tracing.UNPACK), (name, scopes)
        if COLLECTIVE.match(opcode):
            assert scopes[-1] == tracing.PERMUTE, (name, scopes)
        if opcode == "dynamic-update-slice":  # ghosts, or the interior
            assert scopes[-1] in (tracing.UNPACK, tracing.UPDATE), name
        seen.add(scopes[-1])
    want = {tracing.PACK, tracing.UNPACK, tracing.UPDATE}
    if n == 4:  # one device permutes with itself: nothing to send
        want.add(tracing.PERMUTE)
        assert any(COLLECTIVE.match(op) for _, op, _ in ops)
    assert want <= seen


def test_a_plan_counts_its_starts():
    plan = CommPlan(lambda x: x * 2,
                    example_args=(jax.ShapeDtypeStruct((4,), np.float32),))
    assert (plan.starts, plan.start_seconds) == (0, 0.0)
    for _ in range(3):
        out = plan.start(np.ones(4, np.float32))
    np.testing.assert_array_equal(np.asarray(out), np.full(4, 2.0))
    assert plan.starts == 3 and plan.start_seconds > 0
    assert plan.init_seconds > 0


def test_a_cache_sums_the_starts_of_its_plans():
    """``PlanStats.starts`` is the plans' own count, kept past a free."""
    cache = PlanCache()
    args = (jax.ShapeDtypeStruct((4,), np.float32),)
    fn = lambda x: x + 1  # noqa: E731
    plan = cache.get_or_init(fn, args)
    plan.start(np.zeros(4, np.float32))
    plan.start(np.zeros(4, np.float32))
    assert cache.stats.starts == 2
    cache.free_all()
    assert cache.stats.starts == 2 and cache.stats.frees == 1


def test_strategy_plan_counters():
    mesh = make_mesh((1, 1), ("pz", "py"), devices=jax.devices()[:1])
    dom = Domain(mesh, global_interior=(8, 8, 16),
                 mesh_axes=("pz", "py", None))
    x = dom.random(1)
    standard = make_driver("standard", dom.mesh, dom.halo_spec, ndim=3)
    assert standard.plan_counters() is None
    drv = make_driver("persistent", dom.mesh, dom.halo_spec, ndim=3)
    assert drv.plan_counters() is None  # no plan before init
    drv.init(x)
    first = drv.plan_counters()
    assert first["starts"] == 0 and first["init_seconds"] > 0
    for _ in range(4):
        x = drv.step(x)
    drv.wait(x)
    got = drv.plan_counters()
    assert got["starts"] == 4 and got["start_seconds"] > 0
    assert got["init_seconds"] == first["init_seconds"]
    drv.free()


def test_plan_spans_land_in_a_profiler_trace(tmp_path):
    """``plan.build`` and ``plan.start`` are recorded while the profiler
    runs, and cost no annotation when it does not."""
    from jax.profiler import ProfileData

    assert isinstance(tracing.span(tracing.PLAN_START), type(tracing._IDLE))
    args = (jax.ShapeDtypeStruct((4,), np.float32),)
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert isinstance(tracing.span(tracing.PLAN_START),
                          jax.profiler.TraceAnnotation)
        plan = CommPlan(lambda x: x - 1, example_args=args)
        jax.block_until_ready(plan.start(np.ones(4, np.float32)))
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    names = {e.name for plane in ProfileData.from_file(str(path)).planes
             for line in plane.lines for e in line.events}
    assert {tracing.PLAN_BUILD, tracing.PLAN_START} <= names
