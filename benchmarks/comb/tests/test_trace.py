"""The trace reduction and the per-layer readers, on small traces recorded
on one v5e chip by ``record_testdata.py`` (``testdata/``)."""

from __future__ import annotations

from types import SimpleNamespace

import pytest

from benchmarks.comb import run, spec
from benchmarks.comb import trace as tr

DATA = spec.HERE / "testdata"
PEAKS = spec.peaks("TPU v5 lite")


def summary(cell: str, chips: int = 1) -> tr.Summary:
    hlo = (DATA / f"{cell}.hlo.txt").read_text()
    return tr.reduce(DATA / f"{cell}.xplane.pb", chips,
                     tr.scoped_instructions(hlo, run.UPDATE_SCOPE))


def context(cell: str, s: tr.Summary, block=None) -> SimpleNamespace:
    c = spec.load_cell(cell)
    if block:
        c.config["interior_per_chip"] = block
    cycles = len(s.step_starts[0])
    window = run.Window(cycles=1000, elapsed_s=0.5, in_step_s=0.1,
                        batches_s=[5e-4] * 1000, per_wait=1)
    return SimpleNamespace(trace=s, cycles=cycles, window=window,
                           compile_s=1.5, work=run.work_of(c), peaks=PEAKS)


def test_scope_is_read_from_the_hlo():
    hlo = (DATA / "fig2_weak.cycle.hlo.txt").read_text()
    scoped = tr.scoped_instructions(hlo, run.UPDATE_SCOPE)
    assert any(n.startswith("stencil27") for n in scoped)
    assert not any(n.startswith("pack_2d") for n in scoped)
    assert tr.scoped_instructions(hlo, "no_such_scope") == set()


def test_update_and_exchange_are_told_apart():
    s = summary("fig2_weak.cycle")
    update = s.op_seconds(lambda op: op.in_scope)
    exchange = s.op_seconds(lambda op: not op.in_scope)
    assert update > 0 and exchange > 0
    assert s.op_seconds(lambda op: op.collective) == 0  # one chip
    assert 0 < s.busy_s <= s.window_s
    assert update + exchange >= s.busy_s * 0.5


@pytest.mark.parametrize("cell", ["fig2_weak.cycle", "fig4_msg12k.comm"])
def test_summary_and_breakdown(cell):
    s = summary(cell)
    assert s.chips == 1 and len(s.step_starts[0]) >= 3
    b = s.breakdown()
    assert set(b) == {"device_ops", "idle_gaps"}
    assert 0 < len(b["device_ops"]) <= tr.TOP
    assert 0 < len(b["idle_gaps"]) <= tr.TOP
    names = {name for name, _ in b["idle_gaps"]}
    assert names <= {"dispatch", "wait", "host"}
    idle = sum(v for _, v in b["idle_gaps"])
    assert idle == pytest.approx(s.window_s - s.busy_s, rel=1e-6)
    ops = [v for _, v in b["device_ops"]]
    assert ops == sorted(ops, reverse=True) and ops[0] > 0


def test_readers_on_the_cycle_trace():
    s = summary("fig2_weak.cycle")
    ctx = context("fig2_weak.cycle", s, block=[16, 64, 256])
    roof = spec.load_reader("update_roofline")(ctx)
    assert 0 < roof < 100
    idle = spec.load_reader("idle_share.cycle")(ctx)
    assert 0 <= idle < 100
    assert spec.load_reader("compile_s")(ctx) == 1.5


def test_readers_on_the_comm_trace():
    s = summary("fig4_msg12k.comm")
    ctx = context("fig4_msg12k.comm", s)
    assert spec.load_reader("dispatch_us.exchange")(ctx) == pytest.approx(100)
    assert spec.load_reader("exchange_p95_us")(ctx) == pytest.approx(500)
    assert 0 < spec.load_reader("pack_roofline.exchange")(ctx) < 100
    # one chip: no collective op, so the transport's reader finds nothing
    assert spec.load_reader("collective_us.exchange")(ctx) is None
    assert 0 < spec.load_reader("idle_share.exchange")(ctx) < 100


def test_tail_of_the_step_periods():
    """The tail is read over every batch of the untraced window, whose
    times sum to the window as exchange_us's mean does."""
    p95 = spec.load_reader("exchange_p95_us")
    times = [500e-6] * 100
    times[50] = 4500e-6  # one slow cycle in a hundred is not the tail
    w = run.Window(cycles=100, elapsed_s=sum(times), in_step_s=0.01,
                   batches_s=times, per_wait=1)
    assert p95(SimpleNamespace(window=w)) == pytest.approx(500.0)
    assert sum(w.cycle_us()) == pytest.approx(w.elapsed_s * 1e6)
    times[90:] = [500e-6 + 1000e-6 * (i + 1) for i in range(10)]
    assert p95(SimpleNamespace(window=w)) == pytest.approx(6450.0)
    # a wait after 8 cycles: each counts at the batch's time per cycle
    batched = run.Window(cycles=160, elapsed_s=0.08, in_step_s=0.01,
                         batches_s=[4e-3] * 20, per_wait=8)
    assert p95(SimpleNamespace(window=batched)) == pytest.approx(500.0)
    short = run.Window(cycles=10, elapsed_s=0.005, in_step_s=0.001,
                       batches_s=[5e-4] * 10, per_wait=1)
    assert p95(SimpleNamespace(window=short)) is None


def test_dispatch_is_read_from_the_untraced_window():
    w = run.Window(cycles=2000, elapsed_s=1.0, in_step_s=0.25,
                   batches_s=[5e-4] * 2000, per_wait=1)
    read = spec.load_reader("dispatch_us.exchange")
    assert read(SimpleNamespace(window=w, cycles=7)) == pytest.approx(125.0)


def test_collectives_on_four_chips():
    """A 2x2 exchange recorded on four chips: one plane per chip, the
    collective-permutes told apart from the packer's ops."""
    s = summary("fig2_weak.comm.4chip", chips=4)
    assert s.chips == 4 and all(len(v) >= 3 for v in s.step_starts.values())
    collective = s.op_seconds(lambda op: op.collective)
    other = s.op_seconds(lambda op: not op.collective)
    assert collective > 0 and other > 0
    assert s.op_seconds(lambda op: op.in_scope) == 0  # no update
    assert 0 < s.busy_s <= s.window_s
    ctx = context("fig2_weak.comm.4chip", s, block=[16, 64, 256])
    assert spec.load_reader("collective_us.exchange")(ctx) > 0
    assert 0 < spec.load_reader("pack_roofline.exchange")(ctx) < 100
    with pytest.raises(ValueError, match="TPU planes"):
        summary("fig2_weak.comm.4chip", chips=8)


def test_collectives_at_small_faces_read_as_the_transport():
    """The four-chip FIG4 cell's collective reader is the transport's: on
    the same four-chip trace both read the same, and only that cell has
    it among its per-layer metrics."""
    s = summary("fig2_weak.comm.4chip", chips=4)
    ctx = context("fig2_weak.comm.4chip", s, block=[16, 64, 256])
    msg = spec.load_reader("collective_us.exchange.msg12k")(ctx)
    assert msg > 0
    assert msg == spec.load_reader("collective_us.exchange")(ctx)
    has = {cell: "collective_us.exchange.msg12k" in
           {m.name for m in spec.load_cell(cell).per_layer}
           for cell in ("fig4_msg12k.comm.4chip", "fig4_msg12k.comm",
                        "fig2_weak.comm_pipelined", "fig2_weak.cycle",
                        "fig2_weak.comm.4chip")}
    assert has == {"fig4_msg12k.comm.4chip": True, "fig4_msg12k.comm": False,
                   "fig2_weak.comm_pipelined": False,
                   "fig2_weak.cycle": False, "fig2_weak.comm.4chip": False}


def test_readers_find_nothing_in_an_empty_window():
    s = tr.Summary(window=(0.0, 1e9), ops={0: []}, busy={0: []},
                   step_starts={0: []}, host=[])
    window = run.Window(cycles=0, elapsed_s=0.0, in_step_s=0.0,
                        batches_s=[], per_wait=1)
    ctx = SimpleNamespace(trace=s, cycles=0, work={}, peaks=PEAKS,
                          window=window)
    for name in ("update_roofline", "pack_roofline.exchange",
                 "collective_us.exchange", "collective_us.exchange.msg12k",
                 "dispatch_us.exchange", "exchange_p95_us"):
        assert spec.load_reader(name)(ctx) is None
    assert s.busy_s == 0 and s.breakdown()["idle_gaps"] == [["host", 1.0]]


@pytest.mark.parametrize("line, found", [
    ("%collective-permute-start.1 = (f32[8]) collective-permute-start(%x)",
     True),
    ("%all-reduce.3 = f32[] all-reduce(%y), to_apply=%add", True),
    ("%copy.1 = f32[514,1026,1024] copy(%fusion)", False),
    ("%pack_2d.16 = f32[66,128] custom-call(%s)", False),
])
def test_collectives_are_found_by_opcode(line, found):
    assert bool(tr.COLLECTIVE.search(line)) is found
    assert tr.INSTRUCTION.match(line)


def test_union_of_overlapping_intervals():
    assert tr._union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
