"""The paper's workload end-to-end: 3-D Jacobi (heat) iteration on a device
mesh with standard / persistent / partitioned halo exchanges.

Runs in one process on the devices JAX finds: a chip, a TPU host, or
virtual CPU devices pinned from outside, e.g.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        PYTHONPATH=src python examples/stencil_heat3d.py [--cycles 20] [--size 32]
"""

import argparse

import jax
import numpy as np

from repro.core.compat import make_mesh
from repro.core.compile_cache import enable_compile_cache
from repro.kernels.stencil27 import jacobi_weights
from repro.stencil import (
    Domain,
    comb_measure,
    periodic_oracle_step,
    stencil27_update,
)
from repro.stencil.strategies import available_strategies


def mesh_shape(n_devices: int) -> tuple[int, int]:
    """(pz, py) over every device: py takes a factor of 2 where it can."""
    py = 2 if n_devices % 2 == 0 else 1
    return n_devices // py, py


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cycles", type=int, default=10)
    ap.add_argument("--size", type=int, default=32)
    ap.add_argument("--parts", type=int, default=4)
    ap.add_argument("--strategy",
                    choices=[*available_strategies(), "auto"],
                    help="measure+verify just this strategy (against the "
                         "standard baseline); default: all registered, e.g. "
                         "--strategy fused or --strategy overlap; 'auto' "
                         "lets the repro.core.autotune tuner pick strategy, "
                         "packer, and coalesce mode for this cell")
    from repro.core.transport import available_packers

    ap.add_argument("--packer", choices=available_packers(), default="slice",
                    help="transport-layer pack backend every message stages "
                         "through (pallas = the Comb-style copy kernel on "
                         "TPU, its jnp oracle elsewhere)")
    ap.add_argument("--no-coalesce", action="store_true",
                    help="disable wire-buffer coalescing (per-message "
                         "pack/permute/unpack instead of one buffer + one "
                         "composed collective per neighbor hop chain)")
    args = ap.parse_args()
    coalesce = not args.no_coalesce

    enable_compile_cache()
    devices = jax.devices()
    mesh = make_mesh(mesh_shape(len(devices)), ("pz", "py"), devices=devices)
    dom = Domain(mesh, global_interior=(args.size, args.size, args.size // 2),
                 mesh_axes=("pz", "py", None))
    w = jacobi_weights()
    update = stencil27_update(w)

    from repro.stencil import StrategyConfig

    names = (
        tuple(available_strategies()) if args.strategy is None
        else tuple(dict.fromkeys(("standard", args.strategy)))
    )
    strategies = tuple(
        # fully-open autotune cell: the tuner owns packer, coalesce mode,
        # and the partition count, so the CLI pins none of them
        StrategyConfig(name="auto", packer="auto", coalesce="auto")
        if s == "auto" else
        StrategyConfig(
            name=s, packer=args.packer, coalesce=coalesce,
            n_parts=args.parts if s == "partitioned" else 1,
        )
        for s in names
    )
    print(f"domain {dom.global_interior} on mesh {dict(mesh.shape)}; "
          f"{args.cycles} cycles per strategy: {', '.join(names)} "
          f"(packer={args.packer}, "
          f"{'coalesced' if coalesce else 'uncoalesced'})")
    results = comb_measure(dom, strategies=strategies, update_fn=update,
                           n_cycles=args.cycles, repeats=3)
    from repro.stencil.comb import result_label

    base = results[
        result_label("standard", args.packer, coalesce)
    ].us_per_cycle
    for s, r in results.items():
        sp = (base / r.us_per_cycle - 1.0) * 100.0
        print(f"  {s:12s} {r.us_per_cycle:9.1f} us/cycle  "
              f"speedup={sp:+6.1f}%  init={r.init_us:.0f}us")
        if r.selected_by:
            print(f"  {'':12s} resolved to {r.strategy}@{r.packer} "
                  f"{'coalesced' if r.coalesce else 'uncoalesced'} "
                  f"p={r.n_parts} via {r.selected_by} "
                  f"(predicted {r.predicted_us or 0.0:.1f}us, "
                  f"calibration {r.calibration_us / 1e6:.2f}s)")

    # verify against the periodic numpy oracle
    interior = np.random.default_rng(0).normal(
        size=dom.global_interior).astype(np.float32)
    want = interior.copy()
    for _ in range(args.cycles):
        want = periodic_oracle_step(want, np.asarray(w))
    from repro.stencil import make_driver

    verify_with = args.strategy or "persistent"
    verify_config = (
        StrategyConfig(name="auto", packer="auto", coalesce="auto")
        if verify_with == "auto" else
        StrategyConfig(name=verify_with, n_parts=args.parts,
                       packer=args.packer, coalesce=coalesce)
    )
    drv = make_driver(
        verify_config, dom.mesh, dom.halo_spec, ndim=3, update_fn=update,
    )
    x = dom.from_global_interior(interior)
    for _ in range(args.cycles):
        x = drv.step(x)
    got = dom.to_global_interior(drv.wait(x))
    resolved = drv.strategy  # concrete name even when verify_with == "auto"
    drv.free()
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    tag = f"auto→{resolved}" if verify_with == "auto" else verify_with
    print(f"{tag}: verified against periodic numpy oracle ✓")


if __name__ == "__main__":
    main()
