"""``chip_smoke.py``'s phases on the CPU at a tiny domain.

The script itself runs only on a TPU; here its phase functions run on the
conftest's virtual CPU devices, with the ``pallas`` packer pinned to the
Pallas interpreter, to cover the verification logic and the refusal to run
without a chip.  Of the contract line only the shape is checked.
"""

import importlib.util
import json
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core import transport as T
from repro.core.compile_cache import CompileClock
from repro.kernels.stencil27 import jacobi_weights
from repro.stencil import reference_exchange, stencil27_update

ROOT = Path(__file__).resolve().parents[1]
INTERP = "pallas-interpret-smoke"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py"
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def configs(smoke):
    T.register_packer(
        T.PallasPacker(name=INTERP, force_kernel=True, interpret=True)
    )
    try:
        yield smoke.cell_configs(("slice", INTERP))
    finally:
        del T._PACKERS[INTERP]


def test_refuses_without_tpu(smoke, capsys):
    assert jax.default_backend() != "tpu", "test assumes CPU devices"
    with pytest.raises(smoke.NoChip, match="no TPU"):
        smoke.require_tpu(1)
    assert smoke.main([]) != 0
    out, err = capsys.readouterr()
    assert out == "" and "no TPU" in err


def test_cells_cover_every_strategy_and_packer(smoke):
    cells = smoke.cell_configs()
    assert [(c.name, c.packer) for c in cells] == [
        (s, p) for p in smoke.PACKERS for s in smoke.STRATEGIES
    ]
    assert all(c.coalesce is True for c in cells)
    assert {c.n_parts for c in cells if c.name == "partitioned"} == {4}


@pytest.mark.parametrize("mesh_shape", [(1, 1), (2, 2)])
def test_verify_exchanges_tiny(smoke, configs, mesh_shape):
    n = mesh_shape[0] * mesh_shape[1]
    domain = smoke.build_domain(jax.devices()[:n], mesh_shape, (8, 16, 128))
    interior, stored = smoke.make_data(domain, seed=3)
    want = jax.device_put(reference_exchange(domain, interior),
                          domain.sharding())
    lines = []
    custom = smoke.verify_exchanges(domain, stored, want, configs,
                                    emit=lines.append)
    assert list(custom) == [smoke.label(c) for c in configs]
    assert not any(custom.values())  # no Mosaic kernel off the chip
    assert len(lines) == len(configs)


def test_verify_exchanges_detects_a_wrong_exchange(smoke, configs):
    domain = smoke.build_domain(jax.devices()[:1], (1, 1), (8, 16, 128))
    interior, stored = smoke.make_data(domain, seed=3)
    want = reference_exchange(domain, interior)
    want[0, 0, 0] += 1.0  # one ghost cell off
    with pytest.raises(AssertionError, match="differs from reference"):
        smoke.verify_exchanges(
            domain, stored, jax.device_put(want, domain.sharding()),
            configs[:1], emit=lambda _: None,
        )


@pytest.mark.parametrize("mesh_shape", [(1, 1), (2, 2)])
def test_check_oracle_tiny(smoke, configs, mesh_shape):
    n = mesh_shape[0] * mesh_shape[1]
    lines = []
    smoke.check_oracle(jax.devices()[:n], mesh_shape, configs,
                       interpret=True,
                       interior_shape=(4, 8, 16), emit=lines.append)
    assert len(lines) == len(configs)


def test_check_update_tiny(smoke):
    domain = smoke.build_domain(jax.devices()[:4], (2, 2), (8, 16, 128))
    _, stored = smoke.make_data(domain, seed=4)
    diff = smoke.check_update(domain, stored, interpret=True,
                              emit=lambda _: None)
    assert diff <= smoke.UPDATE_TOL


def test_time_cells_tiny(smoke, configs):
    domain = smoke.build_domain(jax.devices()[:1], (1, 1), (4, 8, 16))
    _, stored = smoke.make_data(domain, seed=5)
    rows = smoke.time_cells(
        domain, stored, configs,
        stencil27_update(jacobi_weights(), impl="pallas", interpret=True),
        CompileClock(), n_cycles=1, repeats=1, emit=lambda _: None,
    )
    assert list(rows) == [smoke.label(c) for c in configs]
    for row in rows.values():
        assert row["us_per_cycle"] > 0 and np.isfinite(row["checksum"])
        assert row["compile_s"] >= 0


def test_contract_line_shape(smoke):
    line = json.loads(smoke.contract_line(jax.devices()))
    assert line["ok"] is True
    assert set(line["device"]) == {"platform", "kind", "count"}
    assert line["device"]["count"] == len(jax.devices())
