"""MEASURED benchmark: per-iteration overhead of the three strategies on the
real (CPU) backend, 8 fake devices.

This is the component of the paper's finding that *can* be measured in this
container: the per-iteration plan-assembly + dispatch cost that persistent
plans amortize, and the per-partition op overhead that partitioned adds.
Network transfer time does not exist here, so partitioned shows its overhead
without its overlap win — the paper's own small-message regime (claim C3).

Run standalone (spawns itself with the 8-device XLA flag when needed):
    PYTHONPATH=src python -m benchmarks.measured_dispatch
"""

from __future__ import annotations

import subprocess
import sys


def _run_inner() -> None:
    import jax
    import numpy as np

    from repro.core.compat import make_mesh
    from repro.kernels.stencil27 import jacobi_weights, stencil27_ref
    from repro.stencil import Domain, comb_measure

    mesh = make_mesh((4, 2), ("pz", "py"))
    w = jacobi_weights()

    def update(xl):
        import jax.numpy as jnp

        interior_new = stencil27_ref(xl, jnp.asarray(w))
        return jax.lax.dynamic_update_slice(xl, interior_new, (1, 1, 1))

    for size, parts in ((32, 2), (64, 4)):
        dom = Domain(mesh, global_interior=(size, size, size // 2),
                     mesh_axes=("pz", "py", None))
        res = comb_measure(dom, update_fn=None, n_parts=parts, n_cycles=100,
                           repeats=3)
        base = res["standard"].us_per_cycle
        for s, r in res.items():
            sp = (base / r.us_per_cycle - 1.0) * 100.0
            print(f"measured/halo{size}/{s},{r.us_per_cycle:.1f},"
                  f"speedup={sp:.1f}%;init_us={r.init_us:.0f}")
        # exchange+compute cycles (full Comb iteration)
        res = comb_measure(dom, update_fn=update, n_parts=parts, n_cycles=30,
                           repeats=3)
        base = res["standard"].us_per_cycle
        for s, r in res.items():
            sp = (base / r.us_per_cycle - 1.0) * 100.0
            print(f"measured/cycle{size}/{s},{r.us_per_cycle:.1f},"
                  f"speedup={sp:.1f}%")


def main() -> None:
    """Always spawn a fresh interpreter so the 8-device flag precedes jax init."""
    from repro.launch.stencil import CPU_CHILDREN_NOTE, worker_env

    env = worker_env(local_devices=8)
    print(CPU_CHILDREN_NOTE, flush=True)
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.measured_dispatch", "--inner"],
        env=env, capture_output=True, text=True, timeout=1200,
    )
    sys.stdout.write(out.stdout)
    if out.returncode != 0:
        sys.stderr.write(out.stderr)
        raise SystemExit(out.returncode)


if __name__ == "__main__":
    if "--inner" in sys.argv:
        _run_inner()
    else:
        main()
