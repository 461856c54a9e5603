"""Benchmark harness entry point — one section per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--fast] [--only SECTION[,SECTION]]

Prints ``name,us_per_call,derived`` CSV rows:
  figures      — the paper's four figures (fig2..fig5), projected with the
                 calibrated Quartz-class model (configs/comb_paper.py)
  claims       — model vs the paper's quoted speedups (C1-C6)
  overlap      — HLO structural verification that partitioned exchanges
                 decompose into n_parts independent collectives
  sweep        — the §VI device x partition x message-size grid over all
                 registered strategies -> BENCH_*.json
  fig_sweep    — §VI curves (Fig. 6-8 analogues) rendered from the recorded
                 sweep file, with paper-claim comparisons
  lm           — LM benchmarks (tiny configs, real step timings)

``--only`` runs exactly the named sections (comma separated); the default is
figures+claims, plus everything else unless ``--fast``.
"""

from __future__ import annotations

import argparse
import sys


def emit(name: str, us: float | None, derived: str = "") -> None:
    us_s = f"{us:.2f}" if isinstance(us, (int, float)) and us is not None else ""
    print(f"{name},{us_s},{derived}")


def _section_figures(args) -> None:
    from benchmarks import figures

    print("# === paper figures (calibrated model projection) ===")
    figures.fig2_weak_scaling(emit)
    figures.fig3_strong_scaling(emit)
    figures.fig4_message_size(emit)
    figures.fig5_ranks_per_node(emit)


def _section_claims(args) -> None:
    from benchmarks import figures

    print("# === paper-claim validation (model vs quoted numbers) ===")
    figures.claims_table(emit)


def _section_overlap(args) -> None:
    print("# === partitioned-overlap structure (HLO analysis) ===")
    from benchmarks import overlap_analysis

    overlap_analysis.main()


def _section_sweep(args) -> None:
    print("# === §VI sweep: devices x partitions x message size x packer ===")
    from repro.stencil.sweep import SweepConfig, config_block, run_sweep, \
        summarize, write_bench_json

    config = SweepConfig(device_counts=(2, 4, 8), part_counts=(1, 2, 4),
                         sizes=((32, 16), (64, 32)))
    records = run_sweep(config, timeout=args.sweep_timeout)
    write_bench_json(
        records, args.sweep_out,
        config=config_block(config, timeout=args.sweep_timeout),
    )
    for row in summarize(records):
        print(row)
    print(f"# sweep: {len(records)} records -> {args.sweep_out}")


def _section_fig_sweep(args) -> None:
    print("# === §VI figures (measured sweep vs paper Fig. 6-8) ===")
    from benchmarks import figures

    figures.fig_sweep(emit, sweep_path=args.sweep_out)


def _section_lm(args) -> None:
    print("# === LM benchmarks (tiny configs, real step timings) ===")
    from benchmarks import lm_bench

    lm_bench.main()


#: registration order is run order
SECTIONS = {
    "figures": _section_figures,
    "claims": _section_claims,
    "overlap": _section_overlap,
    "sweep": _section_sweep,
    "fig_sweep": _section_fig_sweep,
    "lm": _section_lm,
}

#: sections skipped under --fast (subprocess-heavy / real timings)
SLOW_SECTIONS = ("overlap", "sweep", "fig_sweep", "lm")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="model-only (skip measured subprocess benchmarks)")
    ap.add_argument("--only", metavar="SECTION[,SECTION]",
                    help=f"run exactly these sections; one or more of: "
                         f"{', '.join(SECTIONS)}")
    ap.add_argument("--sweep-out", default="BENCH_stencil_sweep.json",
                    help="where the §VI sweep writes (and fig_sweep reads) "
                         "its BENCH_*.json records")
    ap.add_argument("--sweep-timeout", type=float, default=1200.0,
                    help="per-subprocess timeout (seconds) for the sweep "
                         "section's device-count fan-out")
    args = ap.parse_args()
    from repro.stencil.sweep import is_bench_path

    if not is_bench_path(args.sweep_out):
        # fail before minutes of sweep subprocesses, not at write time
        ap.error(f"--sweep-out must be named BENCH_*.json, got {args.sweep_out!r}")

    if args.only:
        selected = [s.strip() for s in args.only.split(",") if s.strip()]
        unknown = [s for s in selected if s not in SECTIONS]
        if unknown:
            ap.error(f"unknown section(s) {unknown}; "
                     f"choose from: {', '.join(SECTIONS)}")
    else:
        selected = [
            s for s in SECTIONS if not (args.fast and s in SLOW_SECTIONS)
        ]

    for name in SECTIONS:  # run in registration order regardless of --only order
        if name in selected:
            SECTIONS[name](args)
    print("# done")


if __name__ == "__main__":
    main()
