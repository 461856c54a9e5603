"""``update_copy_us`` on the cycle trace recorded on one v5e chip: the
update's time in ``update_roofline`` splits into the ``stencil27`` kernel
and the copies around it."""

from __future__ import annotations

from types import SimpleNamespace

import pytest

from benchmarks.comb import run, spec
from benchmarks.comb import trace as tr

DATA = spec.HERE / "testdata"
PEAKS = spec.peaks("TPU v5 lite")


def context(cell: str, chips: int = 1, block=(16, 64, 256)):
    hlo = (DATA / f"{cell}.hlo.txt").read_text()
    s = tr.reduce(DATA / f"{cell}.xplane.pb", chips,
                  tr.scoped_instructions(hlo, run.UPDATE_SCOPE))
    c = spec.load_cell(cell)
    c.config["interior_per_chip"] = list(block)
    return SimpleNamespace(trace=s, cycles=len(s.step_starts[0]),
                           work=run.work_of(c), peaks=PEAKS)


def test_update_is_the_kernel_and_its_copies():
    ctx = context("fig2_weak.cycle")
    copies = spec.load_reader("update_copy_us")(ctx)
    roofline = spec.load_reader("update_roofline")(ctx)
    update = ctx.work["update_bytes"] / PEAKS["hbm_bytes_per_s"] / (
        roofline / 100) * 1e6
    kernel = ctx.trace.op_seconds(
        lambda op: op.name.split(".")[0] == "stencil27") / ctx.cycles * 1e6
    assert 0 < copies < update and kernel > 0
    assert update - copies == pytest.approx(kernel)


@pytest.mark.parametrize("cell, chips", [("fig4_msg12k.comm", 1),
                                         ("fig2_weak.comm.4chip", 4)])
def test_nothing_to_read_without_an_update(cell, chips):
    assert spec.load_reader("update_copy_us")(context(cell, chips)) is None
