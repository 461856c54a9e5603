from repro.stencil.domain import (
    Domain,
    periodic_oracle_step,
    reference_exchange,
    stencil27_update,
)
from repro.stencil.exchange import ExchangeDriver
from repro.stencil.strategies import (
    ExchangeStrategy,
    StrategyConfig,
    available_strategies,
    get_strategy,
    make_driver,
    register_strategy,
)
from repro.stencil.comb import (
    CycleResult,
    comb_measure,
    result_label,
    run_cycles,
    speedup_vs_baseline,
)

_SWEEP_EXPORTS = ("SweepConfig", "run_sweep", "sweep_cells",
                  "write_bench_json", "read_bench_json")


def __getattr__(name):
    # lazy: `python -m repro.stencil.sweep` warns if the package body already
    # imported the submodule (runpy sys.modules check).
    if name in _SWEEP_EXPORTS:
        from repro.stencil import sweep

        return getattr(sweep, name)
    raise AttributeError(name)

__all__ = [
    "Domain", "periodic_oracle_step", "reference_exchange", "stencil27_update",
    "ExchangeDriver",
    "ExchangeStrategy", "StrategyConfig", "available_strategies",
    "get_strategy", "make_driver", "register_strategy",
    "CycleResult", "comb_measure", "result_label", "run_cycles",
    "speedup_vs_baseline",
    "SweepConfig", "run_sweep", "sweep_cells", "write_bench_json",
    "read_bench_json",
]
