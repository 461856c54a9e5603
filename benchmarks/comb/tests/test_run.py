"""One run of a tiny cell on the CPU devices: set-up, window and check,
with the update kernel in the Pallas interpreter; the control and the
planted faults must come out not correct.  The command refuses to run
without a TPU."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from benchmarks.comb import run, spec
from benchmarks.comb.tests import tiny

SEED = 2**33 + 12345  # wider than 32 bits, as seeds may be


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    run.import_program()
    return tiny.tiny_checkout(tmp_path_factory.mktemp("checkout"))


@pytest.fixture(autouse=True)
def no_persistent_cache(monkeypatch):
    """Runs here compile without the persistent cache: the variable is
    read by JAX only at start-up, so ``enable_compile_cache`` sets no
    directory; the thresholds it lowers are restored after."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "unused-after-start-up")
    keep = {k: getattr(jax.config, k) for k in (
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    yield
    for k, v in keep.items():
        jax.config.update(k, v)


def one_run(root, name, **kw):
    cell = spec.load_cell(name, root)
    lines = []
    result = run.run_cell(cell, SEED, 0.05, False,
                          jax.devices()[:cell.chips], interpret=True,
                          emit=lines.append, **kw)
    return cell, result, lines


@pytest.mark.parametrize("name", list(tiny.TINY))
def test_tiny_cell_runs_correct(root, name):
    cell, result, lines = one_run(root, name)
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "check"]
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= int(cell.traffic["cycles_per_wait"])
    assert set(result["metrics"]) == {m.name for m in cell.end_to_end}
    for m in cell.end_to_end:
        assert result["metrics"][m.name]["unit"] == m.unit
        assert result["metrics"][m.name]["value"] > 0
    assert result["device"]["count"] == cell.chips
    limits = cell.traffic["limits"]
    assert list(result["check"]) == list(limits)
    for k, limit in limits.items():
        assert result["check"][k]["limit"] == limit
        assert result["check"][k]["value"] == pytest.approx(0.0, abs=1e-6)
    assert any("compile inside the window 0.0 s" in line for line in lines)
    json.dumps(result)


@pytest.mark.parametrize("name", ["tiny.cycle", "tiny.comm.4chip",
                                  "tiny.comm", "tiny_msg.comm.4chip"])
def test_control_is_not_correct(root, name):
    """The program's bf16 wire packer, the precision below float32."""
    _, result, _ = one_run(root, name, packer="bf16")
    assert result["correct"] is False
    mismatches = "ghost_mismatches" if name == "tiny.cycle" else "mismatches"
    assert result["check"][mismatches]["value"] > 0


class Fault:
    """The driver with one fault planted under its ``step``."""

    def __init__(self, inner, kind: str):
        self.inner, self.kind = inner, kind

    def __getattr__(self, name):
        return getattr(self.inner, name)

    def step(self, x):
        inner = self.inner
        if self.kind == "unchanged":
            return x
        if self.kind == "no_exchange":  # the update alone, no exchange
            if inner.update_fn is None:
                return x
            spec_ = P(*[a if a in inner.mesh.shape else None
                        for a in ("pz", "py", None)])
            fn = jax.shard_map(inner.update_fn, mesh=inner.mesh,
                               in_specs=spec_, out_specs=spec_,
                               check_vma=False)
            return jax.jit(fn)(x)
        if self.kind == "half":  # the upper half of z keeps its old state
            old = jnp.copy(x)
            new = inner.step(x)
            z = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
            return jnp.where(z < x.shape[0] // 2, new, old)
        new = inner.step(x)
        cell = (0, 0, 0) if self.kind == "altered_ghost" else (1, 1, 0)
        return new.at[cell].add(1.0)


FAULTS = ["unchanged", "no_exchange", "half", "altered_ghost",
          "altered_interior"]


@pytest.mark.parametrize("name", ["tiny.cycle", "tiny.comm.4chip",
                                  "tiny.comm", "tiny_msg.comm.4chip"])
@pytest.mark.parametrize("kind", FAULTS)
def test_planted_fault_is_not_correct(root, name, kind):
    _, result, _ = one_run(root, name, wrap=lambda d: Fault(d, kind))
    assert result["correct"] is False, result["check"]
    assert result["failed"] == result["attempted"]


class Counter:
    """A driver whose step adds one to every element, its input donated as
    the program's is."""

    def __init__(self):
        self._step = jax.jit(lambda x: x + 1, donate_argnums=0)

    def step(self, x):
        return self._step(x)

    wait = staticmethod(jax.block_until_ready)


@pytest.mark.parametrize("per_wait, ahead", [(1, 0), (8, 0), (2, 3)])
def test_the_window_counts_every_cycle_sent(per_wait, ahead):
    """Every cycle sent is done when the window ends, with batches sent
    ahead or not; the batches' times sum to the window."""
    x = jnp.zeros((4, 8, 128), jnp.float32)
    marks = []
    x, w = run.drive(Counter(), x, 0.3, per_wait, ahead, marks=marks)
    assert w.cycles % per_wait == 0 and w.cycles >= per_wait * (ahead + 1)
    assert float(x[0, 0, 0]) == w.cycles and float(x[-1, -1, -1]) == w.cycles
    assert len(w.batches_s) == w.cycles // per_wait
    assert sum(w.batches_s) == pytest.approx(w.elapsed_s)
    assert w.elapsed_s >= 0.3 and marks == sorted(marks)


def _env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join(
        [str(spec.ROOT / "src"), str(spec.ROOT)]
        + [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p])
    return env


ARGS = ["--workload", "fig2_weak.cycle", "--seed", str(SEED),
        "--seconds", "1", "--trace", "0"]


def test_command_refuses_without_tpu():
    p = subprocess.run([sys.executable, "-m", "benchmarks.comb", *ARGS],
                       cwd=spec.ROOT, env=_env(), capture_output=True,
                       text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout == "" and "no TPU" in p.stderr


def test_command_refuses_in_a_bare_directory(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files."""
    root = tiny.copy_checkout(tmp_path)
    env = _env()
    env["PYTHONPATH"] = str(root)
    p = subprocess.run([sys.executable, "-m", "benchmarks.comb", *ARGS],
                       cwd=root, env=env, capture_output=True, text=True,
                       timeout=120)
    assert p.returncode != 0 and p.stdout == ""


def test_import_loads_no_backend():
    code = (
        "import sys\n"
        "import benchmarks.comb.run, benchmarks.comb.spec, "
        "benchmarks.comb.trace, benchmarks.comb.work, benchmarks.comb.data,"
        " benchmarks.comb.reference, benchmarks.comb.compare, "
        "benchmarks.comb.calibrate, benchmarks.comb.record_testdata\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge._backends, xla_bridge._backends\n"
        "assert not any('libtpu' in m for m in sys.modules)\n"
        "print('clean')\n")
    p = subprocess.run([sys.executable, "-c", code], cwd=spec.ROOT,
                       env=_env(), capture_output=True, text=True,
                       timeout=120)
    assert p.returncode == 0, p.stderr
    assert p.stdout.strip() == "clean"
