"""``BENCHMARK.json`` against its rules, and the cells, traffic mixes and
metrics found by their file names."""

from __future__ import annotations

import json
import re
from types import SimpleNamespace

import pytest

from benchmarks.comb import spec
from benchmarks.comb.tests import tiny

BENCH = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
WHY_RE = re.compile(r"[^\n\t]{1,200}")


def test_benchmark_keys_and_command():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == [str(spec.PKG)]
    assert BENCH["command"] == ["python3", "-m", "benchmarks.comb"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len((spec.ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_configs_cells_and_metrics_keep_the_rules():
    used = {w["config"] for w in BENCH["workloads"]}
    names = []
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["name"] in used
        assert c["file"].startswith(str(spec.PKG) + "/")
        cfg = spec.load_config(spec.ROOT / c["file"], c["name"])
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert WHY_RE.fullmatch(c["why"]) and WHY_RE.fullmatch(c["source"])
        names.append(c["name"])
    pairs = set()
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and WHY_RE.fullmatch(w["why"])
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        names.append(w["name"])
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(CELLS) // 2)
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        names.append(m["name"])
    assert "setup_s" in [m["name"] for m in BENCH["end_to_end"]]
    for m in BENCH["per_layer"]:
        assert m["moves"] in [e["name"] for e in BENCH["end_to_end"]]
        assert WHY_RE.fullmatch(m["layer"])
        names.append(m["name"])
    for n in names:
        spec.check_name(n, "name")
    assert len(names) == len(set(names))


def check_cell_reports(cell: str, root=spec.ROOT) -> list[str]:
    """The rule that every cell keeps, whatever files and entries added it:
    it reports ``setup_s`` and exactly one other end-to-end metric, whose
    quantity (its name before the first ``.``) is its traffic's
    ``metric``, as ``run.run_cell`` maps it; that metric lists the cell in
    its ``workloads``, and no other metric but ``setup_s`` does; and each
    per-layer metric of the cell has a reader and moves one of the cell's
    end-to-end metrics.  Returns the cell's end-to-end names, sorted."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    c = spec.load_cell(cell, root)
    e2e = sorted(m.name for m in c.end_to_end)
    others = [n for n in e2e if n != "setup_s"]
    assert "setup_s" in e2e and len(others) == 1, (cell, e2e)
    assert others[0].split(".")[0] == c.traffic["metric"], (cell, e2e)
    lists = {}
    for m in bench["end_to_end"]:
        if m["name"] != "setup_s":
            assert "workloads" in m, m["name"]
            lists[m["name"]] = m["workloads"]
    assert [n for n, cells in lists.items() if cell in cells] == others
    assert c.per_layer
    for m in c.per_layer:
        assert callable(spec.load_reader(m.name, root))
        assert m.moves in e2e
    return e2e


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_resolves_and_reports(cell):
    check_cell_reports(cell)


def test_mesh_follows_from_the_chips():
    """One block per chip: 1 chip is a 1x1 mesh, 4 chips a 2x2 one, and
    the global interior grows with the mesh (weak scaling)."""
    one, four = (spec.load_cell(c) for c in ("fig2_weak.comm_pipelined",
                                            "fig2_weak.comm.4chip"))
    assert one.mesh_shape == (1, 1) and four.mesh_shape == (2, 2)
    assert one.config["interior_per_chip"] == four.config["interior_per_chip"]
    assert four.global_interior == tuple(
        n * (2 if a else 1) for n, a in zip(one.global_interior,
                                            one.config["mesh_axes"]))


def test_a_configuration_extends_another(tmp_path):
    """The four-chip configuration takes every width of the one it
    extends, so the two cannot drift apart."""
    base = spec.load_config(spec.HERE / "configs" / "comb_fig2_weak.json",
                            "base")
    ici = spec.load_config(spec.HERE / "configs" / "comb_fig2_weak_ici.json",
                           "ici")
    assert "extends" not in ici
    assert {k: v for k, v in ici.items() if k != "deployment"} == {
        k: v for k, v in base.items() if k != "deployment"}
    (tmp_path / "a.json").write_text(json.dumps({"halo": 1, "dtype": "x"}))
    (tmp_path / "b.json").write_text(json.dumps({"extends": "a", "halo": 2}))
    assert spec.load_config(tmp_path / "b.json", "b") == {"halo": 2,
                                                          "dtype": "x"}
    (tmp_path / "c.json").write_text(json.dumps({"extends": "nowhere"}))
    with pytest.raises(spec.SpecError, match="no file"):
        spec.load_config(tmp_path / "c.json", "c")


def test_cells_report_the_metrics_of_their_traffic():
    """The rule holds for every cell, and each cell named here keeps its
    metrics; a cell added by files and entries needs no edit here."""
    got = {cell: check_cell_reports(cell) for cell in CELLS}
    assert got["fig2_weak.cycle"] == ["cycle_us", "setup_s"]
    assert got["fig2_weak.comm_pipelined"] == ["exchange_us.pipelined",
                                               "setup_s"]
    assert got["fig2_weak.comm.4chip"] == ["exchange_us.4chip", "setup_s"]
    assert got["fig4_msg12k.comm"] == ["exchange_us", "setup_s"]
    assert got["fig4_msg12k.comm.4chip"] == ["exchange_us", "setup_s"]
    layer = {cell: {m.name for m in spec.load_cell(cell).per_layer}
             for cell in CELLS}
    assert "collective_us.exchange" in layer["fig2_weak.comm.4chip"]
    assert "pack_roofline.exchange" not in layer["fig4_msg12k.comm"]
    assert "update_roofline" in layer["fig2_weak.cycle"]
    assert "dispatch_us.exchange" not in layer["fig2_weak.comm_pipelined"]
    assert {"dispatch_us.exchange", "exchange_p95_us", "idle_share.exchange",
            "collective_us.exchange.msg12k"} <= layer["fig4_msg12k.comm.4chip"]


@pytest.mark.parametrize("bad", ["a b", "a,b", "a/b", "", ".x", "x" * 65,
                                 "µs"])
def test_bad_names_are_refused(bad):
    with pytest.raises(spec.SpecError):
        spec.check_name(bad, "name")


@pytest.mark.parametrize("bad", ["tokens per second", "", "u" * 17, "µs"])
def test_bad_units_are_refused(bad):
    with pytest.raises(spec.SpecError):
        spec.check_unit(bad, "metric")


@pytest.mark.parametrize("edit", ["unit", "name", "workload", "traffic",
                                  "chips", "ahead"])
def test_a_bad_benchmark_entry_is_refused(tmp_path, edit):
    root = tiny.copy_checkout(tmp_path)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cell = "fig2_weak.comm_pipelined"
    w = next(w for w in bench["workloads"] if w["name"] == cell)
    if edit == "unit":
        bench["end_to_end"][0]["unit"] = "micro seconds"
    elif edit == "name":
        bench["per_layer"][0]["name"] = "compile s"
    elif edit == "workload":
        cell = "no.such.cell"
    elif edit == "traffic":
        w["traffic"] = "no_such_traffic"
    elif edit == "ahead":
        pkg = root / spec.PKG / "traffic"
        t = json.loads((pkg / f"{w['traffic']}.json").read_text())
        t["batches_ahead"] = -1
        (pkg / "bad_ahead.json").write_text(json.dumps(t))
        w["traffic"] = "bad_ahead"
    else:
        w["chips"] = 2
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    with pytest.raises(spec.SpecError):
        spec.load_cell(cell, root)


def test_unknown_device_kind_raises():
    assert spec.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(spec.SpecError, match="no peaks"):
        spec.peaks("TPU v9 imaginary")


def test_a_cell_traffic_and_metric_are_added_by_files_alone(tmp_path):
    """A new traffic mix, configuration, cell and per-layer metric, added
    to a copy of the benchmark as files and entries only, are found."""
    root = tiny.copy_checkout(tmp_path)
    pkg = root / spec.PKG
    traffic = json.loads((pkg / "traffic" / "comm.json").read_text())
    traffic["cycles_per_wait"] = 4
    (pkg / "traffic" / "comm_batched.json").write_text(json.dumps(traffic))
    cfg = json.loads((pkg / "configs" / "comb_fig4_msg12k.json").read_text())
    cfg["interior_per_chip"] = [190, 190, 128]
    (pkg / "configs" / "comb_fig4_msg48k.json").write_text(json.dumps(cfg))
    (pkg / "metrics" / "cycles_seen.py").write_text(
        "def read(ctx):\n    return ctx.cycles or None\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "comb_fig4_msg48k", "source": "a test",
        "file": f"{spec.PKG}/configs/comb_fig4_msg48k.json",
        "reduced": ["ranks", "dtype"], "why": "a test"})
    bench["workloads"].append({
        "name": "fig4_msg48k.comm_batched", "config": "comb_fig4_msg48k",
        "traffic": "comm_batched", "chips": 1, "why": "a test"})
    bench["per_layer"].append({
        "name": "cycles_seen", "unit": "cycles", "better": "higher",
        "source": "host_clock", "layer": "device", "moves": "exchange_us",
        "workloads": ["fig4_msg48k.comm_batched"]})
    for m in bench["end_to_end"]:
        if m["name"] == "exchange_us":
            m["workloads"].append("fig4_msg48k.comm_batched")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = spec.load_cell("fig4_msg48k.comm_batched", root)
    assert cell.traffic["cycles_per_wait"] == 4
    assert cell.global_interior == (190, 190, 128)
    assert "cycles_seen" in [m.name for m in cell.per_layer]
    assert spec.load_reader("cycles_seen", root)(
        SimpleNamespace(cycles=7)) == 7
    # every cell of the copy, the added one too, keeps the spec's rule
    added = [w["name"] for w in bench["workloads"]]
    assert added == CELLS + ["fig4_msg48k.comm_batched"]
    for name in added:
        check_cell_reports(name, root)
    # the real checkout is untouched
    assert "fig4_msg48k.comm_batched" not in CELLS
