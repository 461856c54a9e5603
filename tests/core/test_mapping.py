"""The topology-aware process-to-node mapping layer (repro.launch.mapping).

The tentpole claim is STATIC: from the Message hop tables alone — no
timing, no mesh, no jax collectives — a blocked placement of two 4-rank
nodes on a 2x4 grid strictly reduces the number of inter-node sends vs the
historical row-major placement, for both the sequential and the fused
schedule.  The remaining tests pin the registry contract (permutation
placements, alias resolution, degradation rules), the end-to-end exchange
equivalence of every strategy x mapping on a permuted 8-device mesh, and
the launcher's coordinator-port-race retry (the TOCTOU bugfix riding along
in this change).
"""

import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.core.compat import make_mesh
from repro.core.halo import (
    HaloSpec,
    fused_message_group,
    sequential_message_groups,
)
from repro.core.transport import schedule_locality
from repro.launch.mapping import (
    available_mappings,
    canonical_mapping,
    default_node_size,
    get_mapping,
    mesh_node_ids,
)

MESH_SHAPES = ((8,), (2, 4), (4, 2), (2, 2), (2, 2, 2))
NODE_SIZES = (1, 2, 3, 4, 8)


# ---------------------------------------------------------------------------
# registry contract
# ---------------------------------------------------------------------------


def test_registry_lists_the_three_mappings():
    names = available_mappings()
    assert names == ("row-major", "blocked", "recursive-bisection")
    for name in names:
        assert canonical_mapping(name) == name
        assert get_mapping(name).name == name


def test_alias_resolution():
    assert canonical_mapping("rb") == "recursive-bisection"
    assert get_mapping("rb") is get_mapping("recursive-bisection")


def test_unknown_mapping_fails_with_registered_list():
    with pytest.raises(KeyError, match="row-major"):
        canonical_mapping("hilbert")


@pytest.mark.parametrize("mapping", available_mappings())
@pytest.mark.parametrize("mesh_shape", MESH_SHAPES)
@pytest.mark.parametrize("node_size", NODE_SIZES)
def test_placement_is_a_deterministic_permutation(
    mapping, mesh_shape, node_size
):
    m = get_mapping(mapping)
    n = int(np.prod(mesh_shape))
    placement = m.placement(mesh_shape, node_size)
    assert sorted(placement) == list(range(n))
    # pure function of (shape, node_size): every rank derives the same one
    assert placement == m.placement(mesh_shape, node_size)
    node_of = m.node_of(mesh_shape, node_size)
    assert node_of == tuple(r // node_size for r in placement)


def test_row_major_is_the_identity():
    assert get_mapping("row-major").placement((2, 4), 4) == tuple(range(8))


def test_blocked_exact_placement_on_2x4():
    """Two 4-rank nodes on a (2, 4) grid: blocked tiles each node onto a
    compact 2x2 sub-block instead of stringing it along a row."""
    blocked = get_mapping("blocked")
    assert blocked.block_dims((2, 4), 4) == (2, 2)
    assert blocked.placement((2, 4), 4) == (0, 1, 4, 5, 2, 3, 6, 7)
    assert blocked.node_of((2, 4), 4) == (0, 0, 1, 1, 0, 0, 1, 1)
    # ...whereas row-major strings node 0 along the whole first row
    assert get_mapping("row-major").node_of((2, 4), 4) == (
        0, 0, 0, 0, 1, 1, 1, 1,
    )


@pytest.mark.parametrize("node_size", (1, 3, 8, 16))
def test_blocked_degrades_to_row_major_when_not_blockable(node_size):
    """node_size that is degenerate (<=1, >=n) or does not divide the grid
    must yield a valid placement, never fail: the row-major identity."""
    blocked = get_mapping("blocked")
    assert blocked.block_dims((2, 4), node_size) is None
    assert blocked.placement((2, 4), node_size) == tuple(range(8))


def test_blocked_on_1d_mesh_is_row_major():
    # contiguous ranks along a row ARE already node blocks
    assert get_mapping("blocked").placement((8,), 4) == tuple(range(8))


def test_permute_devices_places_rank_at_coordinate():
    ranks = list(range(8))  # any stand-in device list
    placed = get_mapping("blocked").permute_devices(ranks, (2, 4), 4)
    assert placed == [0, 1, 4, 5, 2, 3, 6, 7]
    assert get_mapping("row-major").permute_devices(ranks, (2, 4), 4) == ranks


def test_default_node_size_rules():
    # multi-process grid: the real devices-per-process count
    assert default_node_size(8, 2) == 4
    assert default_node_size(8, 4) == 2
    # single process: a modeled two-node split keeps an inter-node boundary
    assert default_node_size(8, 1) == 4
    assert default_node_size(4, 1) == 2
    assert default_node_size(1, 1) == 1
    # indivisible grids fall back to the modeled split
    assert default_node_size(8, 3) == 4


# ---------------------------------------------------------------------------
# the tentpole: static hop tables prove the inter-node reduction
# ---------------------------------------------------------------------------

#: two 4-rank nodes on a (2, 4) mesh — the multi-node grid of the claim
GRID = (2, 4)
NODE = 4
SIZES = {"px": GRID[0], "py": GRID[1]}
LOCAL = (14, 8)
SPEC = HaloSpec(mesh_axes=("px", "py"), array_axes=(0, 1), halo=1,
                periodic=True)


def _locality(schedule: str, mapping: str):
    if schedule == "sequential":
        groups = sequential_message_groups(LOCAL, SPEC, SIZES)
    else:
        groups = (fused_message_group(LOCAL, SPEC, SIZES),)
    return schedule_locality(
        groups, axis_order=("px", "py"), axis_sizes=SIZES,
        node_of=get_mapping(mapping).node_of(GRID, NODE),
    )


@pytest.mark.parametrize("schedule", ("sequential", "fused"))
def test_blocked_strictly_reduces_inter_node_sends(schedule):
    """The acceptance table: counted from the static Message tables (no
    timing anywhere), blocked placement strictly reduces inter-node sends
    on the 2x4 two-node grid, for both schedules; recursive bisection
    matches it there.  Total traffic is conserved — mapping only moves
    sends across the node boundary, it never adds or removes any."""
    rm = _locality(schedule, "row-major")
    bl = _locality(schedule, "blocked")
    rb = _locality(schedule, "recursive-bisection")
    assert bl.total_sends == rm.total_sends == rb.total_sends
    assert bl.intra_elems + bl.inter_elems == rm.intra_elems + rm.inter_elems
    assert bl.inter_sends < rm.inter_sends
    assert rb.inter_sends < rm.inter_sends
    # the exact static tally, pinned so a schedule change cannot silently
    # water the claim down
    want_rm, want_bl = {
        "sequential": (16, 8),
        "fused": (48, 24),
    }[schedule]
    assert rm.inter_sends == want_rm
    assert bl.inter_sends == want_bl


def test_locality_tally_is_mapping_independent_in_total():
    """Every mapping sees the same schedule (same tables, same bytes); only
    the intra/inter split moves."""
    totals = {
        m: (_locality("fused", m).total_sends,
            _locality("fused", m).intra_elems
            + _locality("fused", m).inter_elems)
        for m in available_mappings()
    }
    assert len(set(totals.values())) == 1, totals


# ---------------------------------------------------------------------------
# end-to-end: every strategy x mapping still exchanges correct bytes
# ---------------------------------------------------------------------------


@pytest.mark.skipif(len(jax.devices()) < 8,
                    reason="needs 8 virtual devices (conftest)")
@pytest.mark.parametrize("mapping", available_mappings())
@pytest.mark.parametrize(
    "strategy", ("standard", "persistent", "partitioned", "fused", "overlap")
)
def test_exchange_equivalence_on_permuted_mesh(strategy, mapping):
    """The oracle: on a mesh whose device list the mapping permuted, every
    registered strategy's exchange still equals the single-device reference
    roll bitwise — placement moves ranks, never bytes."""
    from repro.stencil.domain import Domain, reference_exchange
    from repro.stencil.strategies import StrategyConfig, make_driver

    mesh_shape, node_size = (4, 2), 2
    devices = get_mapping(mapping).permute_devices(
        jax.devices()[:8], mesh_shape, node_size
    )
    mesh = make_mesh(mesh_shape, ("px", "py"), devices=devices)
    domain = Domain(mesh, global_interior=(8, 6), mesh_axes=("px", "py"))
    rng = np.random.default_rng(7)
    interior = rng.normal(size=domain.global_interior).astype(domain.dtype)
    want = reference_exchange(domain, interior)
    drv = make_driver(
        StrategyConfig(
            name=strategy,
            n_parts=2 if strategy == "partitioned" else 1,
            mapping=mapping,
        ),
        mesh, domain.halo_spec, ndim=2,
    )
    try:
        got = np.asarray(drv.wait(drv.step(
            domain.from_global_interior(interior)
        )))
    finally:
        drv.free()
    np.testing.assert_array_equal(got, want)


@pytest.mark.skipif(len(jax.devices()) < 8,
                    reason="needs 8 virtual devices (conftest)")
def test_mesh_node_ids_reflect_the_permuted_device_list():
    """The live-mesh node derivation agrees with the static node_of vector
    — the ground truth the hop-locality tables classify against."""
    for mapping in available_mappings():
        devices = get_mapping(mapping).permute_devices(
            jax.devices()[:8], (2, 4), 4
        )
        mesh = make_mesh((2, 4), ("px", "py"), devices=devices)
        assert mesh_node_ids(mesh, node_size=4) == (
            get_mapping(mapping).node_of((2, 4), 4)
        )


# ---------------------------------------------------------------------------
# satellite: the coordinator-port TOCTOU retry
# ---------------------------------------------------------------------------


def test_is_port_race_failure_signatures():
    from repro.launch.stencil import is_port_race_failure

    assert is_port_race_failure(
        ["RuntimeError: Address already in use"], [1]
    )
    assert is_port_race_failure(["bind: EADDRINUSE"], [1])
    # a clean exit is never a race, whatever stderr chatters about
    assert not is_port_race_failure(["Address already in use"], [0])
    # real program failures must never be retried into silence
    assert not is_port_race_failure(["AssertionError: chaos"], [1])
    assert is_port_race_failure(
        ["", "failed to bind coordinator port"], [0, 1]
    )


_MARKER_PROG = textwrap.dedent("""
    import sys
    with open(sys.argv[1], "a") as f:
        f.write("attempt\\n")
    print(sys.argv[2], file=sys.stderr)
    sys.exit(int(sys.argv[3]))
""")


def _launch_marker(tmp_path, *, stderr: str, exit_code: int, attempts: int):
    from repro.launch.stencil import launch_grid

    prog = tmp_path / "prog.py"
    prog.write_text(_MARKER_PROG)
    marker = tmp_path / "marker"
    marker.write_text("")
    result = launch_grid(
        [sys.executable, str(prog), str(marker), stderr, str(exit_code)],
        processes=1, local_devices=1, timeout=120.0, check=False,
        attempts=attempts,
    )
    return result, marker.read_text().count("attempt")


def test_launch_grid_retries_port_race_with_fresh_port(tmp_path):
    result, runs = _launch_marker(
        tmp_path, stderr="Address already in use", exit_code=1, attempts=3,
    )
    assert not result.ok
    assert runs == 3  # every bounded attempt actually relaunched


def test_launch_grid_does_not_retry_real_failures(tmp_path):
    result, runs = _launch_marker(
        tmp_path, stderr="AssertionError: genuinely broken", exit_code=1,
        attempts=3,
    )
    assert not result.ok
    assert runs == 1  # non-race failures surface immediately


def test_launch_grid_success_runs_once(tmp_path):
    result, runs = _launch_marker(
        tmp_path, stderr="noise", exit_code=0, attempts=3,
    )
    assert result.ok
    assert runs == 1


def test_launch_grid_check_raises_with_stderr_tail(tmp_path):
    from repro.launch.stencil import launch_grid

    prog = tmp_path / "prog.py"
    prog.write_text(_MARKER_PROG)
    marker = tmp_path / "marker"
    with pytest.raises(RuntimeError, match="genuinely broken"):
        launch_grid(
            [sys.executable, str(prog), str(marker),
             "AssertionError: genuinely broken", "1"],
            processes=1, local_devices=1, timeout=120.0, attempts=2,
        )


# ---------------------------------------------------------------------------
# satellite: zombie workers reaped when the coordinator dies before binding
# ---------------------------------------------------------------------------


_ZOMBIE_PROG = textwrap.dedent("""
    import os, sys, time
    if os.environ["REPRO_PROCESS_ID"] == "0":
        print("coordinator died before binding", file=sys.stderr)
        sys.exit(1)
    time.sleep(600)  # a worker blocked in jax.distributed init
""")


def test_launch_grid_reaps_workers_blocked_on_dead_coordinator(tmp_path):
    """Rank 0 dying before the coordinator binds used to strand the other
    ranks in init for the full grid timeout; the reap reports them in
    failed_ranks within the grace window instead."""
    import time as _time

    from repro.launch.stencil import launch_grid

    prog = tmp_path / "prog.py"
    prog.write_text(_ZOMBIE_PROG)
    t0 = _time.monotonic()
    result = launch_grid(
        [sys.executable, str(prog)],
        processes=2, local_devices=1, timeout=120.0, check=False,
        attempts=1, reap_grace=1.0,
    )
    elapsed = _time.monotonic() - t0
    assert elapsed < 60.0, f"reap did not bound the hang ({elapsed:.0f}s)"
    assert not result.ok
    # BOTH ranks are reported: the dead coordinator and the reaped zombie
    assert result.failed_ranks == (0, 1), result.returncodes
    assert result.returncodes[0] == 1
    assert result.returncodes[1] < 0, "zombie worker was not reaped"
    assert "coordinator died" in result.errs[0]


def test_worker_env_stamps_connect_timeout_and_membership():
    """The REPRO_* grid protocol carries the connect bound and membership
    endpoint alongside the coordinator coordinates — and scrubs both when
    a launch does not provide them (no stale inheritance)."""
    from repro.launch.membership import MEMBERSHIP_VAR
    from repro.launch.stencil import CONNECT_TIMEOUT_VAR, worker_env

    env = worker_env(
        local_devices=2, coordinator="127.0.0.1:9999", num_processes=2,
        process_id=1, base={}, connect_timeout=45.0,
        membership="127.0.0.1:8888",
    )
    assert env[CONNECT_TIMEOUT_VAR] == "45.0"
    assert env[MEMBERSHIP_VAR] == "127.0.0.1:8888"

    stale = {CONNECT_TIMEOUT_VAR: "7", MEMBERSHIP_VAR: "10.0.0.1:1"}
    clean = worker_env(local_devices=2, base=stale)
    assert CONNECT_TIMEOUT_VAR not in clean
    assert MEMBERSHIP_VAR not in clean


def test_worker_env_pins_children_to_cpu():
    """A launcher's children never reach for the chip its parent may hold:
    the worker env pins the CPU whatever the parent's platform."""
    from repro.launch.stencil import worker_env

    env = worker_env(local_devices=2, base={"JAX_PLATFORMS": "tpu"})
    assert env["JAX_PLATFORMS"] == "cpu"
    assert "--xla_force_host_platform_device_count=2" in env["XLA_FLAGS"]
