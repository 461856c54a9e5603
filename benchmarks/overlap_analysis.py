"""Structural (HLO-level) analysis of partitioned-communication overlap.

Without real TPU timing, the partitioned win is verified structurally: the
compiled HLO of a partitioned exchange must contain ``n_parts`` independent
``collective-permute`` rounds per direction (per hop chain when coalesced —
partition rounds stay pipelined either way), interleaved with the per-chunk
pack/unpack compute, so a latency-hiding scheduler can overlap them.  The
fused (standard/persistent) exchange has one collective per direction and no
interleaving freedom.

Reported per configuration:
  * number of collective-permute ops (partitioned == n_parts x fused),
  * wire bytes (must be ~equal: partitioning must not inflate traffic),
  * overlappable fraction = bytes in collectives that have at least one
    independent sibling collective (can be in flight simultaneously).

Run: PYTHONPATH=src python -m benchmarks.overlap_analysis   (spawns 8-dev child)
"""

from __future__ import annotations

import subprocess
import sys


def _run_inner() -> None:
    import jax

    from repro.core.compat import make_mesh
    from repro.core.hlo_analysis import parse_collectives
    from repro.stencil import Domain, ExchangeDriver

    mesh = make_mesh((4, 2), ("pz", "py"))
    dom = Domain(mesh, global_interior=(64, 32, 16),
                 mesh_axes=("pz", "py", None))

    for strategy, parts in (("persistent", 1), ("partitioned", 2),
                            ("partitioned", 4), ("partitioned", 8)):
        for coalesce in (False, True):
            drv = ExchangeDriver(
                dom.mesh,
                lambda s=strategy, p=parts, c=coalesce:
                    dom.halo_spec(s, p).with_(coalesce=c),
                ndim=3, strategy=strategy,
            )
            x = dom.random(0)
            text = drv.compiled_text(x)
            stats = parse_collectives(text, default_group=1)
            n_cp = stats.by_op_counts.get("collective-permute", 0)
            wire = stats.wire_bytes
            label = f"{strategy}_p{parts}/c{int(coalesce)}"
            print(f"overlap/{label}/collective_permutes,{n_cp},"
                  f"wire_bytes={wire:.0f}")
            drv.free()


def main() -> None:
    from repro.launch.stencil import CPU_CHILDREN_NOTE, worker_env

    env = worker_env(local_devices=8)
    print(CPU_CHILDREN_NOTE, flush=True)
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.overlap_analysis", "--inner"],
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
