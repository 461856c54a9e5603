"""LM-side benchmarks: real step timings on tiny configs (CPU) comparing the
paper-technique variants — persistent plan dispatch vs per-call jit, and
fused vs partitioned collectives in the distributed paths (8 fake devices,
structural check + wall time).

Emits ``name,us_per_call,derived`` CSV like the other benchmark sections.
"""

from __future__ import annotations

import subprocess
import sys
import time


def _run_inner() -> None:
    import jax
    import numpy as np

    from repro.core.compat import make_mesh

    from repro.configs import get_config
    from repro.configs.base import OptimizerConfig
    from repro.core.plan import CommPlan, PlanCache
    from repro.models import build_model, concrete_batch
    from repro.parallel.context import ParallelContext
    from repro.train.optimizer import init_opt_state
    from repro.train.train_loop import make_train_step

    mesh = make_mesh((2, 4), ("data", "model"))

    # --- train-step dispatch: persistent plan vs per-call jit path ----------
    cfg = get_config("llama3-8b").reduced()
    model = build_model(cfg)
    opt_cfg = OptimizerConfig(warmup_steps=0, total_steps=100)
    params = model.init(jax.random.key(0))
    state = {"params": params, "opt": init_opt_state(params, opt_cfg)}
    batch = concrete_batch(cfg, 8, 64)
    step = make_train_step(model, opt_cfg)

    plan = CommPlan(step, example_args=(
        jax.eval_shape(lambda: state), jax.eval_shape(lambda: batch)))
    jitted = jax.jit(step)

    def time_it(fn, n=20):
        s, out = state, None
        out = fn(s, batch)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(n):
            out = fn(s, batch)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / n * 1e6

    t_jit = time_it(lambda s, b: jitted(s, b))
    t_plan = time_it(lambda s, b: plan.start(s, b))
    print(f"lm/train_dispatch/jit,{t_jit:.1f},")
    print(f"lm/train_dispatch/persistent_plan,{t_plan:.1f},"
          f"init_us={plan.init_seconds*1e6:.0f}")

    # --- EP MoE: fused vs partitioned all-to-all (8 devices) -----------------
    cfg_m = get_config("phi3.5-moe-42b-a6.6b").reduced()
    model_m = build_model(cfg_m)
    params_m = model_m.init(jax.random.key(1))
    batch_m = concrete_batch(cfg_m, 8, 64, seed=1)
    with jax.set_mesh(mesh):
        for parts, label in ((1, "fused"), (4, "partitioned4")):
            ctx = ParallelContext(mesh=mesh, moe_mode="ep", n_parts=parts)
            fn = jax.jit(lambda p, b, c=ctx: model_m.loss(p, b, ctx=c))
            out = fn(params_m, batch_m)
            jax.block_until_ready(out)
            t0 = time.perf_counter()
            for _ in range(10):
                out = fn(params_m, batch_m)
            jax.block_until_ready(out)
            us = (time.perf_counter() - t0) / 10 * 1e6
            print(f"lm/moe_ep_a2a/{label},{us:.1f},loss={float(out):.4f}")

    # --- ring attention: fused vs partitioned KV exchange --------------------
    cfg_d = get_config("llama3-8b").reduced()
    model_d = build_model(cfg_d)
    params_d = model_d.init(jax.random.key(2))
    batch_d = concrete_batch(cfg_d, 8, 128, seed=2)
    with jax.set_mesh(mesh):
        for parts, label in ((1, "fused"), (4, "partitioned4")):
            ctx = ParallelContext(mesh=mesh, seq_parallel=True, n_parts=parts)
            fn = jax.jit(lambda p, b, c=ctx: model_d.loss(p, b, ctx=c))
            out = fn(params_d, batch_d)
            jax.block_until_ready(out)
            t0 = time.perf_counter()
            for _ in range(10):
                out = fn(params_d, batch_d)
            jax.block_until_ready(out)
            us = (time.perf_counter() - t0) / 10 * 1e6
            print(f"lm/ring_attention/{label},{us:.1f},loss={float(out):.4f}")


def main() -> None:
    from repro.launch.stencil import CPU_CHILDREN_NOTE, worker_env

    env = worker_env(local_devices=8)
    print(CPU_CHILDREN_NOTE, flush=True)
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.lm_bench", "--inner"],
        env=env, capture_output=True, text=True, timeout=1800,
    )
    sys.stdout.write(out.stdout)
    if out.returncode != 0:
        sys.stderr.write(out.stderr)
        raise SystemExit(out.returncode)


if __name__ == "__main__":
    if "--serve" in sys.argv:
        # continuous-batching serve benchmark (repro.serving.bench): the
        # tokens/sec cells over the Message-routed ring-attention path;
        # forwards the remaining flags (--out/--check/--requests/...)
        from repro.serving.bench import main as serve_main

        argv = [a for a in sys.argv[1:] if a != "--serve"]
        raise SystemExit(serve_main(argv))
    if "--inner" in sys.argv:
        _run_inner()
    else:
        main()
