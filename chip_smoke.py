#!/usr/bin/env python3
"""Bring-up smoke of the Comb stencil cycle on TPU, in one process.

    python chip_smoke.py              # one chip: (512, 1024, 1024) f32 domain
    python chip_smoke.py --chips 4    # 2x2 mesh: (1024, 2048, 1024) f32 domain

Every cell is one exchange strategy (``standard``, ``persistent``,
``partitioned`` with 4 parts, ``fused``, ``overlap``) with one packer
(``slice`` or the ``pallas`` pack kernels), coalesced, built by
:func:`repro.stencil.make_driver` on a :class:`repro.stencil.Domain` whose two
leading axes are decomposed over the mesh axes ``("pz", "py")`` and whose last
axis is undecomposed and periodic.  On one chip each mesh axis has size 1, so
the exchange is a periodic self-permute through the same code that runs on N
chips; the y-face is 514 x 1024 values, the paper's weak-scaling face
(``configs/comb_paper.py`` ``FIG2_WEAK``).  The ``--chips 4`` mesh holds the
same 2 GiB block on each chip.

The update is :func:`repro.stencil.stencil27_update` running the
``stencil27`` Pallas kernel.  Before any timing, every cell's exchange is compared bitwise
with :func:`repro.stencil.reference_exchange`, and a ``pallas`` cell whose
compiled step holds no ``tpu_custom_call`` fails.  On one chip the kernel
update's cycles are also compared with the same cycles under XLA, and one
cycle of every cell on a small domain with the numpy
:func:`repro.stencil.periodic_oracle_step`; ``--chips 4`` runs only the
exchange matrix, its comparison and its timing.  Then each cell's cycle
(exchange plus the 27-point update) is timed with
:func:`repro.stencil.comb_measure`.

The last line of standard output is the contract line
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Without a TPU the script exits non-zero and prints no result; it never falls
back to another backend.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path
from typing import Callable, Sequence

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.compat import make_mesh  # noqa: E402
from repro.core.compile_cache import CompileClock, enable_compile_cache  # noqa: E402
from repro.kernels.stencil27 import jacobi_weights  # noqa: E402
from repro.stencil import (  # noqa: E402
    Domain,
    StrategyConfig,
    comb_measure,
    make_driver,
    periodic_oracle_step,
    reference_exchange,
    result_label,
    stencil27_update,
)

STRATEGIES = ("standard", "persistent", "partitioned", "fused", "overlap")
PACKERS = ("slice", "pallas")
N_PARTS = 4
MESH_AXES = ("pz", "py")
#: chips -> (mesh shape, global interior): 2 GiB of f32 interior per chip
LAYOUTS = {
    1: ((1, 1), (512, 1024, 1024)),
    4: ((2, 2), (1024, 2048, 1024)),
}
#: the small domain of the numpy-oracle check (one chip)
ORACLE_INTERIOR = (8, 16, 256)
#: timed cycles per repeat, and repeats, per cell
N_CYCLES, REPEATS = 10, 3
#: kernel vs XLA update after UPDATE_CYCLES cycles: f32, 27 terms each
UPDATE_CYCLES = 2
UPDATE_TOL = 1e-5


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than asked for."""


def require_tpu(chips: int) -> list:
    """The first ``chips`` TPU devices; :class:`NoChip` on any other backend."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(
            f"JAX found no TPU (backend {devices[0].platform!r}); this smoke "
            "runs on the chip only"
        )
    if len(devices) < chips:
        raise NoChip(f"{chips} chips asked for, JAX found {len(devices)}")
    return devices[:chips]


def build_domain(devices: Sequence, mesh_shape: tuple[int, int],
                 interior: tuple[int, int, int]) -> Domain:
    mesh = make_mesh(mesh_shape, MESH_AXES, devices=list(devices))
    return Domain(mesh, global_interior=tuple(interior),
                  mesh_axes=(*MESH_AXES, None))


def cell_configs(packers: Sequence[str] = PACKERS) -> list[StrategyConfig]:
    return [
        StrategyConfig(name=s, packer=p,
                       n_parts=N_PARTS if s == "partitioned" else 1)
        for p in packers for s in STRATEGIES
    ]


def label(cfg: StrategyConfig) -> str:
    return result_label(cfg.name, cfg.packer, cfg.coalesce)


def make_data(domain: Domain, seed: int) -> tuple[np.ndarray, np.ndarray]:
    """Seeded interior and its ghosted stored layout, on the host."""
    rng = np.random.default_rng(seed)
    interior = rng.standard_normal(domain.global_interior, dtype=np.float32)
    return interior, domain.stored_from_interior(interior)


def peak_bytes(devices: Sequence) -> int | None:
    """Largest ``peak_bytes_in_use`` over ``devices`` (None if unreported)."""
    stats = [d.memory_stats() for d in devices]
    if any(s is None for s in stats):
        return None
    return max(s["peak_bytes_in_use"] for s in stats)


@jax.jit
def _bitwise_equal(a: jax.Array, b: jax.Array) -> jax.Array:
    bits = lambda v: jax.lax.bitcast_convert_type(v, jnp.uint32)
    return jnp.all(bits(a) == bits(b))


def verify_exchanges(domain: Domain, stored: np.ndarray, want: jax.Array,
                     configs: Sequence[StrategyConfig],
                     emit: Callable[[str], None] = print) -> dict[str, bool]:
    """One exchange per cell, compared bitwise on the device with ``want``
    (the reference exchange, in the stored layout).  Returns, per cell,
    whether its compiled step holds a ``tpu_custom_call``."""
    spec = jax.ShapeDtypeStruct(stored.shape, stored.dtype,
                                sharding=domain.sharding())
    custom = {}
    for cfg in configs:
        drv = make_driver(cfg, domain.mesh, domain.halo_spec, ndim=3)
        try:
            got = drv.wait(drv.step(jax.device_put(stored, domain.sharding())))
            equal = bool(_bitwise_equal(got, want))
            del got
            custom[label(cfg)] = "tpu_custom_call" in drv.compiled_text(spec)
        finally:
            drv.free()
        if not equal:
            raise AssertionError(
                f"{label(cfg)}: exchange differs from reference_exchange"
            )
        emit(f"verify {label(cfg)}: exchange bitwise-equal to "
             f"reference_exchange; tpu_custom_call="
             f"{'yes' if custom[label(cfg)] else 'no'}")
    return custom


def check_oracle(devices: Sequence, mesh_shape: tuple[int, int],
                 configs: Sequence[StrategyConfig], *,
                 interpret: bool = False, seed: int = 1,
                 interior_shape: tuple[int, int, int] = ORACLE_INTERIOR,
                 emit: Callable[[str], None] = print) -> None:
    """One cycle of every cell at a small size against the numpy oracle,
    with random (asymmetric) weights so a mirrored stencil cannot pass."""
    rng = np.random.default_rng(seed)
    w = rng.normal(size=(3, 3, 3)).astype(np.float32)
    interior = rng.standard_normal(interior_shape, dtype=np.float32)
    want = periodic_oracle_step(interior, w)
    domain = build_domain(devices, mesh_shape, interior_shape)
    update = stencil27_update(w, impl="pallas", interpret=interpret)
    for cfg in configs:
        drv = make_driver(cfg, domain.mesh, domain.halo_spec, ndim=3,
                          update_fn=update)
        try:
            x = drv.wait(drv.step(domain.from_global_interior(interior)))
            got = domain.to_global_interior(x)
        finally:
            drv.free()
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5,
                                   err_msg=label(cfg))
        emit(f"oracle {label(cfg)}: one cycle at {interior_shape} matches "
             "periodic_oracle_step (rtol=atol=1e-5)")


def check_update(domain: Domain, stored: np.ndarray, *,
                 interpret: bool = False,
                 emit: Callable[[str], None] = print) -> float:
    """``UPDATE_CYCLES`` persistent cycles with the kernel update against the
    same cycles with ``stencil27_ref`` under XLA, on the device, with the
    Jacobi weights the timed cells run; returns the largest absolute
    difference (at most ``UPDATE_TOL``)."""
    # the timed weights: with 27 distinct weights XLA materializes the
    # products of stencil27_ref and needs ~14.5 GB of temporaries at the
    # 2 GiB block; the small oracle check carries the asymmetric weights
    w = jacobi_weights()
    finals = {}
    for impl in ("pallas", "xla"):
        drv = make_driver(StrategyConfig(name="persistent"), domain.mesh,
                          domain.halo_spec, ndim=3,
                          update_fn=stencil27_update(w, impl=impl,
                                                     interpret=interpret))
        try:
            x = jax.device_put(stored, domain.sharding())
            for _ in range(UPDATE_CYCLES):
                x = drv.step(x)
            finals[impl] = drv.wait(x)
        finally:
            drv.free()
    diff = float(jnp.max(jnp.abs(finals["pallas"] - finals["xla"])))
    if not diff <= UPDATE_TOL:
        raise AssertionError(
            f"kernel update differs from stencil27_ref by {diff} after "
            f"{UPDATE_CYCLES} cycles (tolerance {UPDATE_TOL})"
        )
    emit(f"update check: {UPDATE_CYCLES} cycles, kernel vs stencil27_ref "
         f"under XLA, max abs diff {diff} (tolerance {UPDATE_TOL})")
    return diff


def time_cells(domain: Domain, stored: np.ndarray,
               configs: Sequence[StrategyConfig],
               update: Callable[[jax.Array], jax.Array], clock: CompileClock,
               *, n_cycles: int, repeats: int,
               emit: Callable[[str], None] = print) -> dict[str, dict]:
    """Time each cell's cycle (exchange + update) through ``comb_measure``;
    the final states' checksums must agree across cells."""
    devices = list(domain.mesh.devices.flat)
    rows = {}
    for cfg in configs:
        compile0 = clock.seconds
        res = comb_measure(
            domain, strategies=(cfg,), update_fn=update, n_cycles=n_cycles,
            repeats=repeats,
            make_input=lambda: jax.device_put(stored, domain.sharding()),
        )[label(cfg)]
        rows[label(cfg)] = row = {
            "us_per_cycle": res.us_per_cycle,
            "init_us": res.init_us,
            "compile_s": clock.seconds - compile0,
            "peak_bytes_in_use": peak_bytes(devices),
            "checksum": res.checksum,
        }
        emit(f"time {label(cfg)}: us/cycle={row['us_per_cycle']} "
             f"init_us={row['init_us']} compile_s={row['compile_s']} "
             f"peak_bytes_in_use={row['peak_bytes_in_use']}")
    sums = {k: r["checksum"] for k, r in rows.items()}
    first = next(iter(sums.values()))
    if any(abs(v - first) > 1e-5 + 1e-5 * abs(first) for v in sums.values()):
        raise AssertionError(f"cells ended in different states: {sums}")
    return rows


def contract_line(devices: Sequence) -> str:
    """The last line of a passing run: the device as JAX reports it."""
    return json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices),
    }})


def main(argv: Sequence[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=sorted(LAYOUTS), default=1,
                    help="1: every phase on one chip; 4: the 2x2 exchange "
                         "matrix and its comparison only")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    try:
        devices = require_tpu(args.chips)
    except NoChip as e:
        print(f"chip_smoke: {e}", file=sys.stderr)
        return 2
    t_start = time.perf_counter()
    cache_dir = enable_compile_cache()
    clock = CompileClock()
    dev0 = devices[0]
    print(f"device: {dev0.platform} {dev0.device_kind}, "
          f"{len(jax.devices())} visible, {len(devices)} used")
    print(f"compile cache: {cache_dir}")

    mesh_shape, interior_shape = LAYOUTS[args.chips]
    domain = build_domain(devices, mesh_shape, interior_shape)
    block_bytes = int(np.prod(domain.local_ghosted)) * 4
    print(f"domain: global interior {interior_shape} f32 on mesh "
          f"{dict(domain.mesh.shape)}; ghosted block "
          f"{domain.local_ghosted} = {block_bytes} bytes per chip; "
          f"face bytes {domain.face_bytes()}")
    print("update: the stencil27 Pallas kernel (stencil27_update, "
          "impl='pallas')")
    configs = cell_configs()

    if args.chips == 1:
        check_oracle(devices, mesh_shape, configs)

    interior, stored = make_data(domain, args.seed)
    want = jax.device_put(reference_exchange(domain, interior),
                          domain.sharding())
    del interior
    custom = verify_exchanges(domain, stored, want, configs)
    del want
    missing = [k for k, cfg in zip(custom, configs)
               if cfg.packer == "pallas" and not custom[k]]
    if missing:
        raise AssertionError(f"pallas cells without tpu_custom_call: {missing}")

    if args.chips == 1:
        check_update(domain, stored)
    update = stencil27_update(jacobi_weights(), impl="pallas")
    time_cells(domain, stored, configs, update, clock,
               n_cycles=N_CYCLES, repeats=REPEATS)
    print(f"total: compile_s={clock.seconds} persistent_cache_hits="
          f"{clock.cache_hits} wall_s={time.perf_counter() - t_start}")
    print(contract_line(jax.devices()))
    return 0


if __name__ == "__main__":
    sys.exit(main())
