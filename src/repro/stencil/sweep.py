"""The paper's §VI parameter study as a reproducible sweep subsystem.

The headline analysis of the paper sweeps *process count*, *thread count*
and *message size* over Comb's exchange strategies.  The JAX-port analogues
swept here:

* **virtual device count**  (process count)  — each device count runs in a
  fresh subprocess with ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
  (the count is fixed at first jax init, so it cannot vary in-process);
* **partition count**       (thread count)   — ``StrategyConfig.n_parts``,
  the number of per-face partitions a partitioned exchange posts;
* **message size**          — the domain's face-slab bytes, varied through
  ``global_interior``;
* **packer**                — the registered transport-layer pack backend
  (``"slice"`` inline staging vs the ``"pallas"`` copy kernel,
  :mod:`repro.core.transport`), swept as a first-class dimension;
* **coalesce**              — wire-buffer message aggregation on/off
  (``StrategyConfig.coalesce``): one contiguous buffer and ONE composed
  collective per hop chain vs the historical per-message pipeline.  The
  uncoalesced first mode hosts the baseline cell.
* **mapping**               — the process-to-node placement
  (:mod:`repro.launch.mapping`): each swept mapping permutes rank placement
  onto the mesh coordinates before the cell's mesh is built (row-major /
  blocked / recursive-bisection), and every record carries the static
  hop-locality tally (``intra_node_sends`` / ``inter_node_sends`` under the
  cell's ``node_size`` ranks-per-node) so the wins show up in the tables,
  not just the timings.  The FIRST mapping hosts the baseline cell.

Each cell's records carry ``packer``, ``transport``, ``coalesce``,
``mapping``, ``node_size``, ``process_count``, ``is_multihost``, ``wire_bytes``,
``collective_count`` (what one step launches — the coalescing effect),
``plan_cache_inits``/``plan_cache_hits`` (the persistent-amortization
counters), and ``replan_us``/``plan_cache_invalidations`` (the elastic
re-planning axis: how long re-deriving the static Message/WireLayout
tables takes for the cell's topology, and how many cached plans a
topology change dropped — see :mod:`repro.launch.elastic`) fields.  The transport backend
(``"ppermute"`` in-process, ``"multihost"`` for multi-process meshes) is
one ``SweepConfig.transport`` knob, and the fan-out is per-*process grid*:
``--processes N`` (``SweepConfig.processes``) boots every device-count cell
as an N-rank ``jax.distributed`` grid through
:func:`repro.launch.stencil.launch_grid` — each rank pins ``n//N`` local
devices, all ranks run the same SPMD measurement, and rank 0 aggregates the
timings into the ordinary BENCH record schema.  Wire-compressed packers
(``bf16``, ``scaled-int8``) shrink ``wire_bytes`` relative to
``message_bytes`` — the compression axis ``fig_sweep`` renders.

Every cell measures all requested registered strategies via
:func:`repro.stencil.comb.comb_measure` and emits one flat record per
(strategy, cell) with the cell's speedup-vs-baseline — the exact quantity
behind the paper's "persistent up to 37% / partitioned up to 68%" numbers.
Records serialize to ``BENCH_<name>.json`` (a json list of row dicts), the
repo's benchmark interchange format.

In-process use (device count fixed to the current backend)::

    records = sweep_cells(SweepConfig(sizes=((64, 32),), part_counts=(1, 4)))

Full sweep (spawns one subprocess per device count)::

    PYTHONPATH=src python -m repro.stencil.sweep --out BENCH_stencil_sweep.json
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import re
import subprocess
import sys
import warnings
from typing import Any, Sequence

SCHEMA_VERSION = 1

#: keys every sweep record carries (validated by tests/stencil/test_sweep.py)
RECORD_KEYS = (
    "bench", "schema_version", "strategy", "n_devices", "n_parts",
    "packer", "transport", "coalesce", "process_count", "is_multihost",
    "mapping", "node_size", "intra_node_sends", "inter_node_sends",
    "global_interior", "mesh_shape", "message_bytes", "wire_bytes",
    "us_per_cycle", "collective_count",
    "plan_cache_inits", "plan_cache_hits",
    "replan_us", "plan_cache_invalidations",
    "selected_by", "predicted_us", "calibration_us",
    "recovery_mode", "join_us", "warm_ranks",
    "init_us", "n_cycles", "repeats", "checksum", "speedup_vs_baseline",
)


def mesh_shape_for(
    n_devices: int, mesh_ndim: int, *, warn: bool = False
) -> tuple[int, ...]:
    """The cell's mesh shape: a 1-D row, or an ``(n/2, 2)`` torus when a
    2-D cell is requested and the device count allows one.

    A 2-D request the device count cannot honor (odd or prime counts)
    silently used to degrade to a 1×N row where no corner chains exist —
    coalescing then measures as a no-op without any trace of why.  With
    ``warn=True`` (the cell-construction sites) the degradation warns, and
    :func:`config_block` records the effective shapes so figures can
    annotate these cells.
    """
    if mesh_ndim == 2:
        if n_devices >= 4 and n_devices % 2 == 0:
            return (n_devices // 2, 2)
        if warn:
            warnings.warn(
                f"mesh_ndim=2 requested but {n_devices} device(s) cannot "
                f"form an (n/2, 2) torus; degrading to the 1-D mesh row "
                f"({n_devices},) — no corner/edge chains exist there, so "
                f"the coalesce axis measures as a no-op for this cell",
                RuntimeWarning,
                stacklevel=2,
            )
    return (n_devices,)


def _assert_decomposable(
    size: tuple[int, ...], mesh_shape: tuple[int, ...], halo: int, why: str
) -> None:
    """The one size-vs-mesh validity rule (config construction AND the
    in-process worker check use it — no drift)."""
    assert len(size) >= len(mesh_shape), (size, mesh_shape)
    for extent, k in zip(size, mesh_shape):
        assert extent % k == 0 and extent // k >= 3 * halo, (
            f"size {size} not decomposable over mesh {mesh_shape}; {why}"
        )


@dataclasses.dataclass(frozen=True)
class SweepConfig:
    """The §VI grid: device count x partition count x message/domain size."""

    device_counts: tuple[int, ...] = (2, 4, 8)
    part_counts: tuple[int, ...] = (1, 2, 4)
    #: global interior shapes; the first axis is decomposed over all devices.
    sizes: tuple[tuple[int, ...], ...] = ((32, 16), (64, 32))
    strategies: tuple[str, ...] = (
        "standard", "persistent", "partitioned", "fused", "overlap",
    )
    #: transport-layer pack backends to sweep (first entry hosts the baseline)
    packers: tuple[str, ...] = ("slice", "pallas")
    #: transport backend every cell's messages move through
    transport: str = "ppermute"
    #: wire-buffer coalescing modes to sweep; the FIRST entry hosts the
    #: baseline cell (default: uncoalesced baseline, then coalesced)
    coalesce_modes: tuple[bool, ...] = (False, True)
    #: process-to-node mappings to sweep (repro.launch.mapping registry);
    #: each mapping builds its own permuted mesh per cell.  The FIRST entry
    #: hosts the baseline cell every speedup is normalized against.
    mappings: tuple[str, ...] = ("row-major",)
    #: ranks (devices) per physical node for the hop-locality tally; 0 =
    #: derive via repro.launch.mapping.default_node_size (process-local
    #: device count on a real grid, a modeled 2-node split in-process)
    node_size: int = 0
    #: jax.distributed grid size per cell (1 = the historical in-process
    #: fan-out; >1 boots each device count as a real multi-process grid)
    processes: int = 1
    #: mesh dimensionality per cell: 1 = the paper's 1-D process row
    #: (historical); 2 = an (n/2, 2) torus decomposing the first two array
    #: axes — edges/corners exist, so wire-buffer coalescing has chains to
    #: merge (the smoke grid uses this)
    mesh_ndim: int = 1
    baseline: str = "standard"
    halo: int = 1
    n_cycles: int = 20
    repeats: int = 2
    seed: int = 0

    def __post_init__(self):
        assert self.baseline in self.strategies, (
            f"baseline {self.baseline!r} must be swept"
        )
        # the baseline denominator must be a deterministic static cell —
        # an autotuned baseline would normalize every speedup against a
        # moving target
        assert self.baseline != "auto", "baseline cannot be autotuned"
        assert self.packers, "at least one packer must be swept"
        assert self.coalesce_modes, "at least one coalesce mode must be swept"
        assert all(isinstance(c, bool) for c in self.coalesce_modes), (
            self.coalesce_modes
        )
        assert len(set(self.coalesce_modes)) == len(self.coalesce_modes), (
            self.coalesce_modes
        )
        assert self.processes >= 1, self.processes
        assert self.node_size >= 0, self.node_size
        assert self.mappings, "at least one mapping must be swept"
        # fail at construction, not minutes later in a worker subprocess
        from repro.core.transport import get_packer, get_transport
        from repro.launch.mapping import canonical_mapping

        canon = tuple(canonical_mapping(m) for m in self.mappings)
        assert len(set(canon)) == len(canon), (
            f"duplicate mapping cells after alias resolution: {self.mappings}"
        )
        object.__setattr__(self, "mappings", canon)
        for p in self.packers:
            get_packer(p)
        get_transport(self.transport)
        assert self.mesh_ndim in (1, 2), self.mesh_ndim
        for n in self.device_counts:
            assert n % self.processes == 0, (
                f"device count {n} not divisible into {self.processes} "
                f"process ranks"
            )
            for size in self.sizes:
                _assert_decomposable(
                    size, mesh_shape_for(n, self.mesh_ndim), self.halo,
                    f"device count {n}",
                )

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self))

    @classmethod
    def from_json(cls, text: str) -> "SweepConfig":
        raw = json.loads(text)
        raw["device_counts"] = tuple(raw["device_counts"])
        raw["part_counts"] = tuple(raw["part_counts"])
        raw["sizes"] = tuple(tuple(s) for s in raw["sizes"])
        raw["strategies"] = tuple(raw["strategies"])
        raw["packers"] = tuple(raw.get("packers", ("slice",)))
        # pre-coalescing config jsons ran the historical uncoalesced path
        # on 1-D mesh rows
        raw["coalesce_modes"] = tuple(
            bool(c) for c in raw.get("coalesce_modes", (False,))
        )
        raw.setdefault("mesh_ndim", 1)
        # pre-mapping config jsons ran the identity placement
        raw["mappings"] = tuple(raw.get("mappings", ("row-major",)))
        raw.setdefault("node_size", 0)
        return cls(**raw)


def _size_records(
    config: SweepConfig, size: tuple[int, ...], n_devices: int
) -> list[dict]:
    """Measure one (device count, size) slab: non-partitioning strategies
    once per packer, partitioning strategies once per (partition count,
    packer), each mapping on its own permuted mesh, all against the same
    baseline run — the first mapping's first-packer first-mode baseline
    strategy — so the packing, coalescing AND placement axes show up in
    the speedup, not as a moving denominator."""
    import jax
    import numpy as _np

    from repro.core.compat import make_mesh
    from repro.core.transport import get_packer, schedule_locality
    from repro.launch.mapping import default_node_size, get_mapping
    from repro.stencil.comb import comb_measure, result_label
    from repro.stencil.domain import Domain
    from repro.stencil.strategies import (
        StrategyConfig,
        get_strategy,
        make_driver,
    )

    mesh_shape = mesh_shape_for(n_devices, config.mesh_ndim, warn=True)
    axis_names = ("px", "py")[: len(mesh_shape)]
    axis_sizes = dict(zip(axis_names, mesh_shape))
    node_size = config.node_size or default_node_size(
        n_devices, jax.process_count()
    )
    n_proc = jax.process_count()
    base_us: float | None = None
    # Message tables are a pure function of (strategy, n_parts, shape,
    # spec) — identical across mappings (test_replan_purity asserts this)
    # — so the hop tables are derived once per (strategy, n_parts) and
    # re-classified under each mapping's node vector.
    groups_cache: dict[tuple[str, int], tuple] = {}
    records: list[dict] = []
    for mapping in config.mappings:
        placed = get_mapping(mapping).permute_devices(
            jax.devices()[:n_devices], mesh_shape, node_size
        )
        mesh = make_mesh(mesh_shape, axis_names, devices=placed)
        domain = Domain(
            mesh,
            global_interior=tuple(size),
            mesh_axes=axis_names + (None,) * (len(size) - len(mesh_shape)),
            halo=config.halo,
        )
        strat_configs = []
        for coalesce in config.coalesce_modes:
            for packer in config.packers:
                knobs = dict(packer=packer, transport=config.transport,
                             coalesce=coalesce, mapping=mapping)
                for s in config.strategies:
                    if s == "auto":
                        continue  # one tuned cell per mapping, added below
                    if get_strategy(s).uses_partitions:
                        strat_configs.extend(
                            StrategyConfig(name=s, n_parts=p, **knobs)
                            for p in config.part_counts
                        )
                    else:
                        # the partition-count axis does not apply: once per
                        # (packer, coalesce mode)
                        strat_configs.append(StrategyConfig(name=s, **knobs))
        if "auto" in config.strategies:
            # the autotuned cell: ONE per mapping — the tuner owns the
            # strategy/packer/coalesce/partition axes, so the static
            # packer x coalesce grid does not multiply it
            strat_configs.append(StrategyConfig(
                name="auto", packer="auto", coalesce="auto",
                transport=config.transport, mapping=mapping,
            ))
        results = comb_measure(
            domain,
            strategies=tuple(strat_configs),
            n_cycles=config.n_cycles,
            repeats=config.repeats,
            seed=config.seed,
        )
        if base_us is None:
            base_us = results[
                result_label(config.baseline, config.packers[0],
                             config.coalesce_modes[0])
            ].us_per_cycle
        node_of = get_mapping(mapping).node_of(mesh_shape, node_size)
        example = jax.ShapeDtypeStruct(
            domain.stored_global, _np.dtype(domain.dtype)
        )
        message_bytes = domain.max_face_bytes()
        face_elems = message_bytes // _np.dtype(domain.dtype).itemsize
        for label, res in results.items():
            key = (res.strategy, res.n_parts)
            if key not in groups_cache:
                drv = make_driver(
                    StrategyConfig(name=res.strategy, n_parts=res.n_parts),
                    domain.mesh, domain.halo_spec, ndim=len(size),
                )
                groups_cache[key] = drv.replan_tables(example)[0]
            loc = schedule_locality(
                groups_cache[key], axis_order=axis_names,
                axis_sizes=axis_sizes, node_of=node_of,
            )
            rec = {
                "bench": "stencil_sweep",
                "schema_version": SCHEMA_VERSION,
                "n_devices": n_devices,
                "process_count": n_proc,
                "is_multihost": n_proc > 1,
                "node_size": node_size,
                "intra_node_sends": loc.intra_sends,
                "inter_node_sends": loc.inter_sends,
                "global_interior": list(size),
                "mesh_shape": list(mesh_shape),
                "message_bytes": message_bytes,
                # what the face actually costs on the wire under this
                # record's packer (compressed packers shrink it)
                "wire_bytes": face_elems
                * get_packer(res.packer).wire_itemsize(domain.dtype),
                "speedup_vs_baseline": base_us / res.us_per_cycle,
                **res.record(),
            }
            records.append(rec)
    return records


def sweep_cells(
    config: SweepConfig, *, n_devices: int | None = None
) -> list[dict]:
    """Run the partition-count x size grid on the current process's devices.

    This is the in-process entry (one device count — the one jax booted
    with); :func:`run_sweep` fans the device-count axis out to subprocesses.
    """
    import jax

    n = n_devices or min(max(config.device_counts), len(jax.devices()))
    assert n <= len(jax.devices()), (n, len(jax.devices()))
    for size in config.sizes:
        _assert_decomposable(
            size, mesh_shape_for(n, config.mesh_ndim), config.halo,
            "this process's device count; pass n_devices= explicitly",
        )
    records = []
    for size in config.sizes:
        records.extend(_size_records(config, size, n))
    return records


# ---------------------------------------------------------------------------
# subprocess fan-out over the device-count axis
# ---------------------------------------------------------------------------


def _worker_env(n_devices: int) -> dict[str, str]:
    # the ONE worker-environment recipe (device pin + PYTHONPATH) lives
    # with the launch harness; no coordinator -> plain single-process env.
    from repro.launch.stencil import worker_env

    return worker_env(local_devices=n_devices)


def run_sweep(config: SweepConfig, *, timeout: float = 1200.0) -> list[dict]:
    """The full §VI grid: one worker run per device count (the device-count
    flag must precede jax init), each emitting its cells' records as json
    on stdout.

    With ``config.processes == 1`` each device count is one fresh
    subprocess (the historical in-process fan-out).  With ``processes > 1``
    each device count boots as a real N-rank ``jax.distributed`` grid via
    :func:`repro.launch.stencil.launch_grid`: every rank pins ``n // N``
    local devices, the same worker entry point runs SPMD on the global
    mesh, and only rank 0 prints the aggregated records.
    """
    records: list[dict] = []
    for n in config.device_counts:
        sub = dataclasses.replace(config, device_counts=(n,))
        argv = [sys.executable, "-m", "repro.stencil.sweep",
                "--worker", sub.to_json()]
        if config.processes > 1:
            from repro.launch.stencil import launch_grid

            stdout = launch_grid(
                argv, processes=config.processes,
                local_devices=n // config.processes, timeout=timeout,
            )
        else:
            out = subprocess.run(
                argv, env=_worker_env(n), capture_output=True, text=True,
                timeout=timeout,
            )
            if out.returncode != 0:
                raise RuntimeError(
                    f"sweep worker ({n} devices) failed:\n{out.stderr[-4000:]}"
                )
            stdout = out.stdout
        records.extend(json.loads(stdout))
    return records


def is_bench_path(path: str) -> bool:
    """The one definition of the ``BENCH_*.json`` naming rule."""
    base = os.path.basename(path)
    return base.startswith("BENCH_") and base.endswith(".json")


def write_bench_json(
    records: Sequence[dict], path: str, *, config: dict | None = None
) -> None:
    """Serialize records to the repo's ``BENCH_*.json`` interchange format.

    Without ``config`` the file is the historical bare list of row dicts;
    with it, records are wrapped as ``{"config": ..., "records": [...]}``
    so the run's parameters (grid, packers, transport, subprocess timeout)
    travel with the measurements.  :func:`read_bench_json` accepts both.
    """
    assert is_bench_path(path), path
    payload: Any = (
        list(records) if config is None
        else {"config": config, "records": list(records)}
    )
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)
        f.write("\n")


def read_bench_json(path: str) -> tuple[list[dict], dict | None]:
    """Load a ``BENCH_*.json`` file: (records, config-block-or-None).

    Malformed payloads raise :class:`ValueError` naming the file and the
    shape mismatch — not a bare ``KeyError`` from deep inside a consumer
    (the regression guard's historical failure mode on stale baselines).
    """
    with open(path) as f:
        payload = json.load(f)
    if isinstance(payload, dict):
        if "records" not in payload:
            raise ValueError(
                f"{path}: BENCH dict payload has no 'records' key (top-level"
                f" keys: {sorted(payload)}); expected the bare record list "
                f"or the {{'config': ..., 'records': [...]}} wrapper — the "
                f"file is not a BENCH interchange artifact"
            )
        return list(payload["records"]), payload.get("config")
    if not isinstance(payload, list):
        raise ValueError(
            f"{path}: BENCH payload must be a json list or dict, got "
            f"{type(payload).__name__}"
        )
    return list(payload), None


def summarize(records: Sequence[dict]) -> list[str]:
    """csv rows (name,us,derived) matching benchmarks/run.py's emit format.

    The name carries the full cell coordinate including the PR 7 mapping
    axis; the derived column carries the locality tally
    (``intra=``/``inter=`` node sends) and, for autotuned records, the
    selection provenance — an ``auto:`` tag also prefixes the resolved
    strategy so a tuned cell never collides with the identical static one.
    """
    rows = []
    for r in records:
        tag = "auto:" if r.get("selected_by") else ""
        name = (f"sweep/d{r['n_devices']}/p{r['n_parts']}"
                f"/m{r['message_bytes']}/{r.get('packer', 'slice')}"
                f"/c{int(bool(r.get('coalesce', False)))}"
                f"/{r.get('mapping', 'row-major')}"
                f"/{tag}{r['strategy']}")
        pct = (r["speedup_vs_baseline"] - 1.0) * 100.0
        derived = (f"speedup={pct:.1f}%;init_us={r['init_us']:.0f};"
                   f"replan_us={r.get('replan_us', 0.0):.0f}")
        if "intra_node_sends" in r or "inter_node_sends" in r:
            derived += (f";intra={r.get('intra_node_sends', 0)}"
                        f";inter={r.get('inter_node_sends', 0)}")
        if r.get("selected_by"):
            derived += f";selected_by={r['selected_by']}"
        rows.append(f"{name},{r['us_per_cycle']:.1f},{derived}")
    return rows


def regression_failures(
    baseline_records: Sequence[dict],
    records: Sequence[dict],
    *,
    threshold: float = 0.25,
) -> list[str]:
    """Compare a fresh sweep against a committed baseline sweep.

    Per *strategy* present in BOTH record sets, the best
    ``speedup_vs_baseline`` across all its cells must not fall more than
    ``threshold`` below the committed best.  Speedups (not absolute
    microseconds) are compared, so the guard survives CI machines of
    different speeds; keying by strategy (not per-cell coordinate) keeps
    the max over ~a dozen cells, whose run-to-run noise is far below any
    single tiny cell's — single-cell jitter on the 3-cycle smoke grid
    exceeds 25%, so a finer key would flash red on identical code.  Only
    ``speedup_vs_baseline`` is compared: newer record fields (e.g. the
    ``replan_us`` re-plan latency or ``plan_cache_invalidations``) are
    tolerated in either record set and simply travel along — a baseline
    written before a field existed never trips the guard.  The
    check is only meaningful when both runs swept comparable grids (CI
    runs it on the full-matrix smoke job, never the restricted ``--packer``
    cells).  Returns human-readable failure lines (empty = pass).

    Autotuned records (``selected_by`` set) are NOT keyed by their resolved
    strategy name — that would let a ``strategy=auto`` sweep satisfy the
    guard by merely resolving to the same names.  They pool under one
    ``auto`` key whose best speedup must clear the committed autotuned best
    when the baseline carries one, else the committed *best static* cell —
    the tuner's whole contract is matching the static oracle, so falling
    ``threshold`` below it is a selection regression even if every static
    path is healthy.

    A record missing the two keys the guard actually reads (``strategy``,
    ``speedup_vs_baseline``) raises :class:`ValueError` naming the record
    and the likely cause (a baseline predating the schema), instead of the
    historical bare ``KeyError``.
    """

    def best(recs: Sequence[dict], which: str) -> tuple[
        dict[str, float], float | None
    ]:
        """(per-strategy best of the STATIC records, best autotuned-or-None)."""
        static: dict[str, float] = {}
        auto: float | None = None
        for i, r in enumerate(recs):
            for key in ("strategy", "speedup_vs_baseline"):
                if key not in r:
                    raise ValueError(
                        f"{which} record {i} is missing {key!r} "
                        f"(schema_version={r.get('schema_version')!r}): the "
                        f"file likely predates the current record schema — "
                        f"regenerate it with `python -m repro.stencil.sweep "
                        f"--smoke --out BENCH_stencil_sweep.json`"
                    )
            if r.get("selected_by"):
                auto = max(r["speedup_vs_baseline"],
                           auto if auto is not None else 0.0)
            else:
                static[r["strategy"]] = max(r["speedup_vs_baseline"],
                                            static.get(r["strategy"], 0.0))
        return static, auto

    old, old_auto = best(baseline_records, "baseline")
    new, new_auto = best(records, "fresh-sweep")
    fails = []
    if new_auto is not None:
        if old_auto is not None:
            ref, ref_label = old_auto, "committed autotuned best"
        elif old:
            ref = max(old.values())
            ref_label = "committed best static cell"
        else:
            raise ValueError(
                "fresh sweep carries autotuned records but the baseline has "
                "no records to floor them against — the baseline predates "
                "the autotune schema; regenerate it with `python -m "
                "repro.stencil.sweep --smoke --out BENCH_stencil_sweep.json`"
            )
        floor = ref * (1.0 - threshold)
        if new_auto < floor:
            fails.append(
                f"auto: best autotuned speedup {new_auto:.3f} fell below "
                f"{floor:.3f} ({ref_label} {ref:.3f}, threshold "
                f"{threshold:.0%})"
            )
    compared_auto = new_auto is not None
    if (old or new) and not set(old) & set(new) and not compared_auto:
        raise ValueError(
            f"no strategy appears in BOTH record sets (baseline strategies "
            f"{sorted(old)}, fresh {sorted(new)}): the sweeps are not "
            f"comparable — a stale baseline or mismatched grids would make "
            f"this guard silently vacuous"
        )
    for strategy in sorted(set(old) & set(new)):
        floor = old[strategy] * (1.0 - threshold)
        if new[strategy] < floor:
            fails.append(
                f"{strategy}: best speedup {new[strategy]:.3f} fell below "
                f"{floor:.3f} (committed {old[strategy]:.3f}, threshold "
                f"{threshold:.0%})"
            )
    return fails


def check_against_baseline(
    records: Sequence[dict], baseline_path: str, *, threshold: float = 0.25
) -> list[str]:
    """CLI helper: load the committed BENCH baseline and diff ``records``."""
    baseline_records, _config = read_bench_json(baseline_path)
    return regression_failures(baseline_records, records,
                               threshold=threshold)


def smoke_config(
    n_devices: int = 4,
    packers: tuple[str, ...] | None = None,
    coalesce_modes: tuple[bool, ...] | None = None,
    mappings: tuple[str, ...] | None = None,
    strategies: tuple[str, ...] | None = None,
) -> SweepConfig:
    """A 1-cell grid over ALL registered strategies x ALL registered
    packers (incl. the wire-compressed ones) x both coalesce modes x two
    process-to-node mappings (row-major baseline + blocked) — the
    CI ``sweep-smoke`` step: any strategy, packer, coalesce, or placement
    path whose exchange regresses (crashes, diverges, loses its speedup
    record) surfaces here in seconds.

    The decomposed extent scales with the device count (4 cells per
    shard), so the smoke grid stays valid at any ``--processes`` fan-out
    — the face (message) size is along the decomposed axis and does not
    change with it.
    """
    from repro.core.transport import available_packers
    from repro.stencil.strategies import available_strategies

    return SweepConfig(
        device_counts=(n_devices,), part_counts=(1, 2),
        sizes=((4 * n_devices, 8),),
        strategies=(
            tuple(available_strategies()) if strategies is None
            else strategies
        ),
        n_cycles=3, repeats=1,
        packers=available_packers() if packers is None else packers,
        coalesce_modes=(
            (False, True) if coalesce_modes is None else coalesce_modes
        ),
        # row-major hosts the baseline; blocked exercises a genuinely
        # permuted mesh (on the (2, 2) torus its node vector differs)
        mappings=(
            ("row-major", "blocked") if mappings is None else mappings
        ),
        # a 2-D (n/2, 2) torus: edges/corners exist, so the coalesce axis
        # has hop chains to merge (3 vs 12 collectives for a fused cell)
        mesh_ndim=2,
    )


def config_block(
    config: SweepConfig,
    *,
    timeout: float,
    smoke: bool = False,
    processes: int | None = None,
) -> dict:
    """The BENCH config block: the full grid + run parameters (incl. the
    subprocess ``timeout``) and runtime provenance, so a recorded sweep is
    re-runnable as-is.  The one schema for every writer (this CLI and
    ``benchmarks.run``).

    ``processes`` is the per-cell grid size the records were measured
    under; it defaults to this process's own ``jax.process_count()`` —
    callers writing on behalf of a spawned grid (the ``--processes``
    fan-out, whose launcher never joins the grid) must pass the real
    count.
    """
    import jax

    n_proc = (max(config.processes, jax.process_count())
              if processes is None else processes)
    return {
        "sweep": dataclasses.asdict(config),
        "timeout": timeout,
        "smoke": smoke,
        "backend": jax.default_backend(),
        "process_count": n_proc,
        "is_multihost": n_proc > 1,
        # the mesh each device count ACTUALLY ran on (a 2-D request can
        # degrade to a 1-D row — see mesh_shape_for's warning)
        "effective_mesh_shapes": {
            str(n): list(mesh_shape_for(n, config.mesh_ndim))
            for n in config.device_counts
        },
    }


def main(argv: Sequence[str] | None = None) -> None:
    from repro.launch.stencil import CPU_CHILDREN_NOTE

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--worker", metavar="CONFIG_JSON",
                    help="(internal) run one device-count's cells in-process")
    ap.add_argument("--out", default="BENCH_stencil_sweep.json",
                    help="output path (must match BENCH_*.json)")
    ap.add_argument("--fast", action="store_true",
                    help="2-cell smoke grid instead of the full default grid")
    ap.add_argument("--smoke", action="store_true",
                    help="1-cell in-process grid over all registered "
                         "strategies x packers (no subprocess fan-out; CI "
                         "smoke)")
    ap.add_argument("--packer", metavar="NAME",
                    help="restrict the packer axis to ONE registered packer "
                         "(default: sweep the config's packers)")
    ap.add_argument("--coalesce", choices=("on", "off", "both"),
                    default="both",
                    help="restrict the wire-buffer coalescing axis "
                         "(default: sweep both modes; the uncoalesced cell "
                         "hosts the baseline when present)")
    ap.add_argument("--mapping", metavar="NAME",
                    help="restrict the process-to-node mapping axis to ONE "
                         "registered mapping (row-major|blocked|rb), or "
                         "'all' to sweep every registered mapping "
                         "(default: the config's mappings)")
    ap.add_argument("--strategy", metavar="NAMES",
                    help="comma list of strategies to sweep; 'all' = every "
                         "registered strategy (the default), 'auto' = the "
                         "autotuned cell (repro.core.autotune picks the "
                         "best strategy x packer x coalesce per cell).  The "
                         "static baseline is always swept alongside, so "
                         "speedups keep their denominator")
    ap.add_argument("--autotune-trace", metavar="BENCH_JSON",
                    help="BENCH sweep the autotuner's trace-driven cost "
                         "model fits from (sets REPRO_AUTOTUNE_TRACE for "
                         "this run and every worker subprocess)")
    ap.add_argument("--autotune-cache", metavar="PATH",
                    help="persistent autotune calibration-verdict cache "
                         "(sets REPRO_AUTOTUNE_CACHE; default "
                         "~/.cache/repro/autotune.json)")
    ap.add_argument("--check", metavar="BENCH_JSON",
                    help="after the run, diff the records against this "
                         "committed BENCH baseline and exit non-zero if any "
                         "strategy's speedup regressed beyond the threshold")
    ap.add_argument("--check-threshold", type=float, default=0.25,
                    help="allowed fractional speedup regression for --check "
                         "(default 0.25)")
    ap.add_argument("--processes", type=int, default=1,
                    help="boot every device-count cell as an N-rank "
                         "jax.distributed grid (real multihost transport; "
                         "each rank pins devices/N local devices and rank 0 "
                         "aggregates the records)")
    ap.add_argument("--timeout", type=float, default=1200.0,
                    help="per-subprocess timeout (seconds) for the "
                         "device-count fan-out; recorded in the BENCH "
                         "config block")
    args = ap.parse_args(argv)

    if args.worker:
        # may be one rank of a --processes grid: join it before jax boots
        from repro.launch.stencil import maybe_initialize_from_env

        rank = maybe_initialize_from_env()
        config = SweepConfig.from_json(args.worker)
        import jax

        assert jax.process_count() == config.processes, (
            jax.process_count(), config.processes,
        )
        records = sweep_cells(config, n_devices=config.device_counts[0])
        if rank == 0:
            print(json.dumps(records))
        return

    if args.processes < 1:
        ap.error(f"--processes must be >= 1, got {args.processes}")

    if not is_bench_path(args.out):
        ap.error(f"--out must be named BENCH_*.json, got {args.out!r}")

    if args.packer:
        from repro.core.transport import available_packers

        if args.packer not in available_packers():
            ap.error(f"--packer must be one of {available_packers()}, "
                     f"got {args.packer!r}")

    coalesce_modes = {"on": (True,), "off": (False,), "both": None}[
        args.coalesce
    ]

    mappings: tuple[str, ...] | None = None
    if args.mapping:
        from repro.launch.mapping import available_mappings, canonical_mapping

        if args.mapping == "all":
            mappings = available_mappings()
        else:
            try:
                mappings = (canonical_mapping(args.mapping),)
            except KeyError as e:
                ap.error(str(e.args[0]) if e.args else str(e))

    # the autotuner's inputs travel by env var so worker subprocesses (which
    # copy os.environ) resolve "auto" cells from the same trace and share
    # the same persistent calibration cache
    if args.autotune_trace:
        os.environ["REPRO_AUTOTUNE_TRACE"] = args.autotune_trace
    if args.autotune_cache:
        os.environ["REPRO_AUTOTUNE_CACHE"] = args.autotune_cache

    strategies: tuple[str, ...] | None = None
    if args.strategy and args.strategy != "all":
        from repro.stencil.strategies import available_strategies

        names = tuple(s.strip() for s in args.strategy.split(",") if s.strip())
        for s in names:
            if s != "auto" and s not in available_strategies():
                ap.error(
                    f"--strategy must name registered strategies "
                    f"{available_strategies()} or 'auto', got {s!r}"
                )
        # the static baseline always rides along: every record's speedup is
        # normalized against it, and the guard's auto-vs-best-static floor
        # needs at least one static cell
        baseline = SweepConfig.__dataclass_fields__["baseline"].default
        strategies = tuple(dict.fromkeys((baseline,) + names))

    def maybe_check(records) -> None:
        if not args.check:
            return
        fails = check_against_baseline(records, args.check,
                                       threshold=args.check_threshold)
        if fails:
            for line in fails:
                print(f"REGRESSION: {line}", file=sys.stderr)
            raise SystemExit(1)
        print(f"# regression check vs {args.check}: ok")

    if args.smoke:
        if args.processes > 1:
            # a real grid cannot be joined from this already-running
            # process: spawn the 1-cell smoke as an N-rank worker grid
            # (2 local devices per rank) through the multihost transport.
            config = smoke_config(
                2 * args.processes,
                packers=(args.packer,) if args.packer else None,
                coalesce_modes=coalesce_modes,
                mappings=mappings,
                strategies=strategies,
            )
            config = dataclasses.replace(
                config, processes=args.processes, transport="multihost",
            )
            print(CPU_CHILDREN_NOTE)
            records = run_sweep(config, timeout=args.timeout)
        else:
            # in-process: the device count must be pinned before jax
            # initializes.  An already-exported pin (a common local
            # setting) is honored — the smoke grid runs at that count —
            # rather than silently fighting the env and tripping a
            # device-count mismatch.
            pin = re.search(
                r"--xla_force_host_platform_device_count=(\d+)",
                os.environ.get("XLA_FLAGS", ""),
            )
            n = int(pin.group(1)) if pin else 4
            if pin is None:
                os.environ["XLA_FLAGS"] = (
                    os.environ.get("XLA_FLAGS", "")
                    + f" --xla_force_host_platform_device_count={n}"
                ).strip()
            config = smoke_config(
                n, packers=(args.packer,) if args.packer else None,
                coalesce_modes=coalesce_modes,
                mappings=mappings,
                strategies=strategies,
            )
            records = sweep_cells(config, n_devices=n)
        write_bench_json(
            records, args.out,
            config=config_block(config, timeout=args.timeout, smoke=True,
                                processes=args.processes),
        )
        for row in summarize(records):
            print(row)
        print(f"# smoke: {len(records)} records -> {args.out}")
        maybe_check(records)
        return

    config = SweepConfig()
    if args.fast:
        config = dataclasses.replace(
            config, device_counts=(2, 4), part_counts=(1, 2), sizes=((32, 16),)
        )
    if args.packer:
        config = dataclasses.replace(config, packers=(args.packer,))
    if coalesce_modes is not None:
        config = dataclasses.replace(config, coalesce_modes=coalesce_modes)
    if mappings is not None:
        config = dataclasses.replace(config, mappings=mappings)
    if strategies is not None:
        config = dataclasses.replace(config, strategies=strategies)
    if args.processes > 1:
        config = dataclasses.replace(
            config, processes=args.processes, transport="multihost",
        )
    print(CPU_CHILDREN_NOTE)
    records = run_sweep(config, timeout=args.timeout)
    write_bench_json(records, args.out,
                     config=config_block(config, timeout=args.timeout,
                                         processes=args.processes))
    for row in summarize(records):
        print(row)
    print(f"# wrote {len(records)} records -> {args.out}")
    maybe_check(records)


if __name__ == "__main__":
    main()
