"""A copy of the benchmark's files with tiny cells beside the real ones,
for runs on the CPU."""

from __future__ import annotations

import json
import shutil
from pathlib import Path

from benchmarks.comb import spec

#: tiny cell -> (the real cell it mirrors, chips, block per chip); it takes
#: the real cell's traffic, metrics and configuration, at its own block
TINY = {
    "tiny.cycle": ("fig2_weak.cycle", 1, [8, 16, 128]),
    "tiny.comm": ("fig2_weak.comm_pipelined", 1, [8, 16, 128]),
    "tiny.comm.4chip": ("fig2_weak.comm.4chip", 4, [8, 16, 128]),
    "tiny.cycle.4chip": ("fig2_weak.cycle", 4, [8, 16, 128]),
    "tiny_msg.comm": ("fig4_msg12k.comm", 1, [6, 6, 128]),
    "tiny_msg.comm.4chip": ("fig4_msg12k.comm.4chip", 4, [6, 6, 128]),
}


def copy_checkout(dest: Path) -> Path:
    """``BENCHMARK.json`` and the benchmark's directory, copied to ``dest``."""
    shutil.copy(spec.ROOT / "BENCHMARK.json", dest / "BENCHMARK.json")
    shutil.copytree(spec.HERE, dest / spec.PKG,
                    ignore=shutil.ignore_patterns("__pycache__", "tests",
                                                  "testdata"))
    return dest


def tiny_checkout(dest: Path) -> Path:
    """A copy whose ``BENCHMARK.json`` also holds the ``TINY`` cells."""
    root = copy_checkout(dest)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    configs = {c["name"]: c for c in bench["configs"]}
    cells = {w["name"]: w for w in bench["workloads"]}
    for name, (real, chips, block) in TINY.items():
        base = configs[cells[real]["config"]]
        cfg = spec.load_config(root / base["file"], base["name"])
        cfg["interior_per_chip"] = block
        config = f"tiny_{name.replace('.', '_')}"
        path = spec.PKG / "configs" / f"{config}.json"
        (root / path).write_text(json.dumps(cfg))
        bench["configs"].append(dict(base, name=config, file=str(path)))
        bench["workloads"].append(dict(cells[real], name=name, config=config,
                                       chips=chips))
        for m in bench["end_to_end"] + bench["per_layer"]:
            if real in m.get("workloads", ()):
                m["workloads"].append(name)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root
