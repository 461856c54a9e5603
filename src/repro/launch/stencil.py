"""Multi-process stencil launcher — the real backend behind the ``multihost``
transport seam.

    PYTHONPATH=src python -m repro.launch.stencil --processes 2 \\
        --strategies all --packers slice,bf16 --size 16,8

Boots N worker processes under ``jax.distributed.initialize`` (the first
rank hosts the coordinator service, the paper's ``mpirun -np N`` analogue),
each pinning its own ``--devices-per-process`` virtual CPU devices, then
builds ONE global mesh spanning every process and runs the requested
strategy x packer cells through the ``multihost`` transport.  Every cell is
verified shard-by-shard against the single-process reference roll
(:func:`repro.stencil.domain.reference_exchange`) before it is timed with
:func:`repro.stencil.comb.comb_measure`, so a cell that moves wrong bytes
across the process boundary can never report a speedup.

The launch pattern mirrors ``repro.launch.train``: the coordinator address
travels in env vars (here ``REPRO_COORDINATOR`` / ``REPRO_NUM_PROCESSES`` /
``REPRO_PROCESS_ID``, set by :func:`worker_env`), and a worker calls
:func:`maybe_initialize_from_env` *before its first jax device query* —
anything launched through :func:`launch_grid` (this CLI, the sweep's
``--processes`` fan-out, ``tests/distributed_progs/check_multihost.py``)
joins the same grid protocol.  On a real cluster the same worker code runs
under the site launcher by exporting the three variables per rank.
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import re
import socket
import subprocess
import sys
import tempfile
import time
from typing import Any, Callable, Mapping, Sequence

#: env vars carrying the grid coordinates to worker processes
COORDINATOR_VAR = "REPRO_COORDINATOR"
NUM_PROCESSES_VAR = "REPRO_NUM_PROCESSES"
PROCESS_ID_VAR = "REPRO_PROCESS_ID"
#: bound (seconds) on a worker's connect to the rank-0 coordinator — a
#: worker whose coordinator died before binding exits instead of blocking
#: in ``jax.distributed`` init forever
CONNECT_TIMEOUT_VAR = "REPRO_CONNECT_TIMEOUT"

_DEVICE_FLAG = "--xla_force_host_platform_device_count"
#: printed by every launcher whose children :func:`worker_env` pins to the
#: CPU, ahead of the children's output
CPU_CHILDREN_NOTE = (
    "# child processes run on virtual CPU devices (JAX_PLATFORMS=cpu): "
    "their times are CPU times"
)


def pick_coordinator_port() -> int:
    """A free TCP port for the rank-0 coordinator service.

    Inherently racy (TOCTOU): the port is bound, released, and only later
    re-bound by ``jax.distributed`` inside the rank-0 worker — under
    parallel CI jobs another process can steal it in between.  The race
    cannot be closed from here (the coordinator must bind it in a *child*
    process), so :func:`launch_grid` treats a coordinator bind failure as
    retryable and relaunches with a fresh port (bounded attempts).
    """
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


#: stderr signatures of the coordinator losing the picked port to the
#: TOCTOU race above — and nothing else: injected chaos failures, assertion
#: deaths, or OOMs must never be retried into silence.
_PORT_RACE_SIGNATURES = (
    "address already in use",
    "eaddrinuse",
    "failed to bind",
    "errno 98",
)


def is_port_race_failure(errs: Sequence[str],
                         returncodes: Sequence[int]) -> bool:
    """Did this grid die because the coordinator port was stolen?"""
    return any(
        rc != 0 and any(sig in err.lower() for sig in _PORT_RACE_SIGNATURES)
        for err, rc in zip(errs, returncodes)
    )


def worker_env(
    *,
    local_devices: int,
    coordinator: str | None = None,
    num_processes: int = 1,
    process_id: int = 0,
    base: Mapping[str, str] | None = None,
    connect_timeout: float | None = None,
    membership: str | None = None,
) -> dict[str, str]:
    """The environment one worker process boots with.

    Pins the worker to the CPU (``JAX_PLATFORMS=cpu``): a parent that holds
    a chip must never start children that reach for it.  Pins exactly
    ``local_devices`` virtual CPU devices (replacing any
    device-count pin inherited from the parent — the launcher may itself
    run under the 8-device test env — while preserving other XLA flags)
    and prepends this checkout's ``src`` to ``PYTHONPATH`` so spawned
    workers resolve the same ``repro``.  With ``coordinator`` set the grid
    coordinates are stamped too; without it this is the plain
    single-process worker env (what the sweep's historical device-count
    fan-out boots).
    """
    env = dict(os.environ if base is None else base)
    flags = re.sub(rf"{_DEVICE_FLAG}=\d+", "", env.get("XLA_FLAGS", ""))
    env["XLA_FLAGS"] = f"{flags} {_DEVICE_FLAG}={local_devices}".strip()
    env["JAX_PLATFORMS"] = "cpu"
    from repro.launch.membership import MEMBERSHIP_VAR

    if coordinator is not None:
        env[COORDINATOR_VAR] = coordinator
        env[NUM_PROCESSES_VAR] = str(num_processes)
        env[PROCESS_ID_VAR] = str(process_id)
    else:
        for var in (COORDINATOR_VAR, NUM_PROCESSES_VAR, PROCESS_ID_VAR):
            env.pop(var, None)  # never inherit stale grid coordinates
    # connect bound + membership endpoint follow the same rule: stamped
    # when this launch provides them, scrubbed otherwise
    if connect_timeout is not None and coordinator is not None:
        env[CONNECT_TIMEOUT_VAR] = str(connect_timeout)
    else:
        env.pop(CONNECT_TIMEOUT_VAR, None)
    if membership is not None:
        env[MEMBERSHIP_VAR] = membership
    else:
        env.pop(MEMBERSHIP_VAR, None)
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    return env


def maybe_initialize_from_env() -> int:
    """Join the process grid named by the ``REPRO_*`` env vars; return rank.

    No-op (rank 0 of a 1-process world) when the variables are absent, so
    worker entry points stay runnable standalone.  Must be called before
    the process's first jax device query: ``jax.distributed.initialize``
    cannot attach once the backend client exists.
    """
    coordinator = os.environ.get(COORDINATOR_VAR)
    if not coordinator:
        return 0
    import jax

    from repro.core import compat

    num_processes = int(os.environ[NUM_PROCESSES_VAR])
    process_id = int(os.environ[PROCESS_ID_VAR])
    connect_timeout = os.environ.get(CONNECT_TIMEOUT_VAR)
    compat.distributed_initialize(
        coordinator_address=coordinator,
        num_processes=num_processes,
        process_id=process_id,
        timeout=float(connect_timeout) if connect_timeout else None,
    )
    assert jax.process_count() == num_processes, (
        jax.process_count(), num_processes,
    )
    return process_id


@dataclasses.dataclass(frozen=True)
class GridResult:
    """Per-rank outcome of a ``check=False`` grid launch.

    The chaos tests launch grids that are *expected* to die mid-run (an
    injected rank loss); they need the returncodes and streams of every
    rank instead of the raise-on-failure contract.
    """

    outs: tuple[str, ...]
    errs: tuple[str, ...]
    returncodes: tuple[int, ...]

    @property
    def ok(self) -> bool:
        return all(rc == 0 for rc in self.returncodes)

    @property
    def failed_ranks(self) -> tuple[int, ...]:
        return tuple(r for r, rc in enumerate(self.returncodes) if rc != 0)


def _launch_grid_once(
    argv: Sequence[str],
    *,
    processes: int,
    local_devices: int,
    timeout: float,
    env: Mapping[str, str] | None,
    reap_grace: float = 10.0,
    membership: bool = False,
) -> GridResult:
    """One grid attempt against a freshly picked coordinator port.

    A rank exiting nonzero dooms the whole SPMD grid, so the wait is a
    poll: once the first failure lands, the remaining ranks get
    ``reap_grace`` seconds to die on their own (collective errors
    propagate), then any still-running rank is reaped and reported in
    :attr:`GridResult.failed_ranks`.  Without the reap, a worker whose
    coordinator died before binding blocks in ``jax.distributed`` init
    for the full grid ``timeout`` — the zombie-grid CI hang.  The
    worker-side half of the same fix is the ``REPRO_CONNECT_TIMEOUT``
    bound stamped into every rank's env.

    With ``membership`` a port for the rank-0 membership service
    (:mod:`repro.launch.membership`) is picked here and advertised to
    every rank through ``REPRO_MEMBERSHIP``; the rank-0 program binds it
    via :func:`repro.launch.membership.serve_from_env`.
    """
    coordinator = f"127.0.0.1:{pick_coordinator_port()}"
    membership_addr = (
        f"127.0.0.1:{pick_coordinator_port()}" if membership else None
    )
    procs, files = [], []
    deadline = time.monotonic() + timeout
    reap_at = None  # set when the first rank dies nonzero
    try:
        for rank in range(processes):
            # spool each rank's streams to temp files: every rank drains
            # concurrently (a chatty rank can never fill a pipe and stall
            # the collectives of the whole grid)
            out_f = tempfile.TemporaryFile(mode="w+")
            err_f = tempfile.TemporaryFile(mode="w+")
            files.append((out_f, err_f))
            procs.append(subprocess.Popen(
                list(argv),
                env=worker_env(
                    coordinator=coordinator, num_processes=processes,
                    process_id=rank, local_devices=local_devices, base=env,
                    connect_timeout=timeout, membership=membership_addr,
                ),
                stdout=out_f, stderr=err_f, text=True,
            ))
        while any(p.poll() is None for p in procs):
            now = time.monotonic()
            if now >= deadline:  # ONE shared wall-clock budget
                raise RuntimeError(
                    f"grid did not complete within {timeout:.0f}s "
                    f"({sum(p.poll() is None for p in procs)} of "
                    f"{processes} ranks still running)"
                )
            if reap_at is None and any(
                    p.poll() is not None and p.returncode != 0
                    for p in procs):
                reap_at = min(now + reap_grace, deadline)
            if reap_at is not None and now >= reap_at:
                for p in procs:  # reap the blocked zombies
                    if p.poll() is None:
                        p.kill()
                        p.wait()
                break
            time.sleep(0.05)
    finally:
        for p in procs:  # one rank dying must not strand the others
            if p.poll() is None:
                p.kill()
                p.wait()
        outs, errs = [], []
        for out_f, err_f in files:
            out_f.seek(0)
            err_f.seek(0)
            outs.append(out_f.read())
            errs.append(err_f.read())
            out_f.close()
            err_f.close()
    return GridResult(
        outs=tuple(outs), errs=tuple(errs),
        returncodes=tuple(p.returncode for p in procs),
    )


def launch_grid(
    argv: Sequence[str],
    *,
    processes: int,
    local_devices: int = 2,
    timeout: float = 900.0,
    env: Mapping[str, str] | None = None,
    check: bool = True,
    attempts: int = 3,
    reap_grace: float = 10.0,
    membership: bool = False,
) -> str | GridResult:
    """Run ``argv`` as an N-process ``jax.distributed`` grid; return rank
    0's stdout.

    All ranks execute the same SPMD program; by convention only rank 0
    prints results (the others' stdout is discarded).  Any rank exiting
    nonzero fails the whole grid with that rank's stderr tail — mirroring
    ``run_sweep``'s single-subprocess error contract.  With ``check=False``
    no rank failure raises: the full :class:`GridResult` (every rank's
    stdout/stderr/returncode) is returned instead, for callers that
    *expect* the grid to die — the fault-injection chaos checks.

    Coordinator setup retries: :func:`pick_coordinator_port` is racy by
    construction, so a grid whose failure stderr matches a port-bind
    signature (:func:`is_port_race_failure`) is relaunched with a fresh
    port, up to ``attempts`` total tries.  Only bind failures retry —
    chaos-injected deaths and real program failures surface immediately
    (and reach ``check=False`` callers as their :class:`GridResult`).
    The wall-clock ``timeout`` applies per attempt.
    """
    assert processes >= 1, processes
    assert attempts >= 1, attempts
    for attempt in range(1, attempts + 1):
        result = _launch_grid_once(
            argv, processes=processes, local_devices=local_devices,
            timeout=timeout, env=env, reap_grace=reap_grace,
            membership=membership,
        )
        if result.ok or not (
            attempt < attempts
            and is_port_race_failure(result.errs, result.returncodes)
        ):
            break
        print(
            f"# launch_grid: coordinator port stolen (attempt {attempt} of "
            f"{attempts}); retrying with a fresh port",
            file=sys.stderr,
        )
    if not check:
        return result
    if not result.ok:
        detail = "\n".join(
            f"--- rank {r} (exit {result.returncodes[r]}) ---\n"
            f"{result.errs[r][-4000:]}"
            for r in result.failed_ranks
        )
        raise RuntimeError(
            f"grid ranks {list(result.failed_ranks)} of {processes} "
            f"failed:\n{detail}"
        )
    return result.outs[0]


# ---------------------------------------------------------------------------
# worker-side cell runner (verify + measure on the global mesh)
# ---------------------------------------------------------------------------


def global_stencil_mesh(
    n_devices: int | None = None,
    *,
    mapping: str = "row-major",
    node_size: int = 0,
):
    """A 1-axis mesh over the grid's *global* device list.

    After ``jax.distributed.initialize`` every process sees the same
    ``jax.devices()`` ordering, so each rank independently builds an
    identical mesh spanning all processes.  ``mapping`` permutes rank
    placement onto mesh coordinates through the registered
    :class:`repro.launch.mapping.Mapping` BEFORE the mesh is built (the
    placement is deterministic, so every rank still derives the same mesh);
    ``node_size`` is the ranks-per-node the mapping blocks around
    (0 = auto: devices per process on a real grid).  ``mapping="auto"``
    resolves to the registered mapping minimizing inter-node neighbor
    sends on this topology (:func:`repro.core.autotune.choose_mapping`) —
    mapping is the one autotuned axis that must resolve *before* the mesh
    exists, since a built mesh cannot be re-placed.
    """
    import jax

    from repro.core.compat import make_mesh
    from repro.launch.mapping import default_node_size, get_mapping

    devices = jax.devices()
    n = n_devices or len(devices)
    assert n <= len(devices), (n, len(devices))
    if node_size <= 0:
        node_size = default_node_size(n, jax.process_count())
    if mapping == "auto":
        from repro.core.autotune import choose_mapping

        mapping = choose_mapping((n,), node_size)
    placed = get_mapping(mapping).permute_devices(
        devices[:n], (n,), node_size
    )
    return make_mesh((n,), ("px",), devices=placed)


def verify_strategy_cell(
    domain,
    *,
    strategy: str,
    packer: str = "slice",
    transport: str = "multihost",
    n_parts: int = 3,
    seed: int = 7,
    coalesce: bool = True,
    mapping: str = "row-major",
) -> None:
    """One correctness cell: exchange on the (possibly multi-process) mesh,
    then compare every *addressable* shard against the reference roll.

    Exact packers are held to bitwise equality — the bytes that crossed the
    process boundary must be the bytes the single-process oracle predicts;
    wire-compressed packers are held to their own documented
    :meth:`~repro.core.transport.Packer.wire_tolerance`.
    """
    import numpy as np

    from repro.core.transport import get_packer
    from repro.stencil.domain import reference_exchange
    from repro.stencil.strategies import StrategyConfig, make_driver

    rng = np.random.default_rng(seed)
    interior = rng.normal(size=domain.global_interior).astype(domain.dtype)
    want = reference_exchange(domain, interior)
    drv = make_driver(
        StrategyConfig(
            name=strategy, n_parts=n_parts, packer=packer,
            transport=transport, coalesce=coalesce, mapping=mapping,
        ),
        domain.mesh, domain.halo_spec, ndim=len(domain.global_interior),
    )
    try:
        got = drv.wait(drv.step(domain.from_global_interior(interior)))
    finally:
        drv.free()
    rtol, atol = get_packer(packer).wire_tolerance(domain.dtype)
    for shard in got.addressable_shards:
        data = np.asarray(shard.data)
        ref = want[shard.index]
        msg = (f"{strategy}@{packer}/{transport} n_parts={n_parts} "
               f"coalesce={coalesce} "
               f"shard={shard.index} (rank {shard.device.process_index})")
        if rtol == 0.0 and atol == 0.0:
            np.testing.assert_array_equal(data, ref, err_msg=msg)
        else:
            np.testing.assert_allclose(data, ref, rtol=rtol, atol=atol,
                                       err_msg=msg)


def run_cell(
    *,
    size: tuple[int, ...],
    strategies: Sequence[str],
    packers: Sequence[str],
    transport: str = "multihost",
    halo: int = 1,
    n_parts: int = 3,
    n_cycles: int = 10,
    repeats: int = 1,
    seed: int = 0,
    mapping: str = "row-major",
    emit: Callable[[str], Any] = print,
) -> list[dict]:
    """Verify + measure the strategy x packer cells on the global mesh.

    Returns the flat BENCH-style records of :func:`repro.stencil.comb.
    comb_measure` (one per cell) — callers decide what rank prints.
    """
    import jax

    from repro.stencil.comb import comb_measure
    from repro.stencil.domain import Domain
    from repro.stencil.strategies import StrategyConfig, get_strategy

    if mapping == "auto":
        # resolve BEFORE any StrategyConfig sees it: the placement axis is
        # fixed at mesh construction, so it cannot stay symbolic downstream
        from repro.core.autotune import choose_mapping
        from repro.launch.mapping import default_node_size

        n_all = len(jax.devices())
        mapping = choose_mapping(
            (n_all,), default_node_size(n_all, jax.process_count())
        )
        emit(f"# mapping=auto resolved to {mapping}")
    mesh = global_stencil_mesh(mapping=mapping)
    n = len(mesh.devices.flat)
    assert size[0] % n == 0 and size[0] // n >= 3 * halo, (size, n)
    domain = Domain(
        mesh, global_interior=tuple(size),
        mesh_axes=("px",) + (None,) * (len(size) - 1), halo=halo,
    )
    configs = []
    for packer in packers:
        for s in strategies:
            if s == "auto":
                parts = 1  # the tuner owns the partition-count axis
            else:
                parts = n_parts if get_strategy(s).uses_partitions else 1
            verify_strategy_cell(
                domain, strategy=s, packer=packer, transport=transport,
                n_parts=parts, mapping=mapping,
            )
            emit(f"VERIFIED {s}@{packer}/{transport} on {n} devices "
                 f"across {jax.process_count()} processes")
            configs.append(StrategyConfig(
                name=s, n_parts=parts, packer=packer, transport=transport,
                mapping=mapping,
            ))
    results = comb_measure(
        domain, strategies=tuple(configs),
        n_cycles=n_cycles, repeats=repeats, seed=seed,
    )
    records = []
    for label, res in results.items():
        rec = {
            "label": label,
            "n_devices": n,
            "process_count": jax.process_count(),
            "is_multihost": jax.process_count() > 1,
            "global_interior": list(size),
            **res.record(),
        }
        records.append(rec)
        emit(f"{label}: {res.us_per_cycle:.1f} us/cycle "
             f"(init {res.init_us:.0f} us)")
    return records


def main(argv: Sequence[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--processes", type=int, default=2,
                    help="process-grid size (ranks under jax.distributed)")
    ap.add_argument("--devices-per-process", type=int, default=2,
                    help="virtual CPU devices each rank pins")
    ap.add_argument("--strategies", default="all",
                    help="comma list of registered strategies, 'all', or "
                         "'auto' (repro.core.autotune picks the strategy "
                         "per cell)")
    ap.add_argument("--packers", default="slice",
                    help="comma list of registered packers, or 'all'")
    ap.add_argument("--transport", default="multihost",
                    help="registered transport every cell routes through")
    ap.add_argument("--mapping", default="row-major",
                    help="registered process-to-node mapping permuting rank "
                         "placement onto the mesh (row-major|blocked|rb), "
                         "or 'auto' to pick the one minimizing inter-node "
                         "neighbor sends on this topology")
    ap.add_argument("--size", default="16,8",
                    help="global interior shape, comma-separated")
    ap.add_argument("--halo", type=int, default=1)
    ap.add_argument("--n-parts", type=int, default=3)
    ap.add_argument("--n-cycles", type=int, default=10)
    ap.add_argument("--repeats", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--timeout", type=float, default=900.0,
                    help="per-rank wall-clock limit (seconds)")
    args = ap.parse_args(argv)

    from repro.launch.mapping import canonical_mapping

    if args.mapping != "auto":
        try:  # fail in the launcher, not N spawned ranks deep
            canonical_mapping(args.mapping)
        except KeyError as e:
            ap.error(str(e))

    if COORDINATOR_VAR not in os.environ:
        # launcher: re-run this same CLI as an N-rank grid
        out = launch_grid(
            [sys.executable, "-m", "repro.launch.stencil", *sys.argv[1:]]
            if argv is None else
            [sys.executable, "-m", "repro.launch.stencil", *argv],
            processes=args.processes,
            local_devices=args.devices_per_process,
            timeout=args.timeout,
        )
        print(CPU_CHILDREN_NOTE)
        print(out, end="")
        return

    # worker: join the grid, then run the cells; only rank 0 reports
    rank = maybe_initialize_from_env()
    from repro.core.transport import available_packers
    from repro.stencil.strategies import available_strategies

    strategies = (available_strategies() if args.strategies == "all"
                  else tuple(args.strategies.split(",")))
    packers = (available_packers() if args.packers == "all"
               else tuple(args.packers.split(",")))
    size = tuple(int(s) for s in args.size.split(","))
    emit = print if rank == 0 else (lambda *_: None)
    records = run_cell(
        size=size, strategies=strategies, packers=packers,
        transport=args.transport, halo=args.halo, n_parts=args.n_parts,
        n_cycles=args.n_cycles, repeats=args.repeats, seed=args.seed,
        mapping=args.mapping, emit=emit,
    )
    emit(f"# {len(records)} multihost cells OK")


if __name__ == "__main__":
    main()
