"""System-level benchmarks beyond the paper's figures: plan-reuse vs per-call
decomposition, Pallas kernel roofline characterization, Tucker
gradient-compression wire savings, and tiny-train throughput (the end-to-end
driver measured)."""

from __future__ import annotations

import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.optim.grad_compress import CompressionConfig, compressed_bytes

from .common import emit, lowrank_tensor, time_call


def _bench_backends() -> tuple[str, ...]:
    """Backend axis for the system benches: jnp backends everywhere; the
    ``pallas`` rows only where they mean something (native TPU) or when
    forced via ``ATUCKER_BENCH_PALLAS=1`` (interpret mode — correctness
    trajectory, not perf)."""
    import os
    backends = ["matfree", "explicit"]
    if jax.default_backend() == "tpu" or os.environ.get("ATUCKER_BENCH_PALLAS"):
        backends.append("pallas")
    return tuple(backends)


def plan_bench(n_repeat: int = 8, batch: int = 8):
    """Plan/execute vs legacy per-call API (the tentpole's amortization claim).

    Three regimes per shape:
      * percall  — legacy ``sthosvd(x, ranks, methods="auto")``: selector +
        Python dispatch inside every call.
      * plan     — ``plan()`` once, then repeated ``execute``: frozen schedule,
        one cached compiled sweep — one row per ops backend.
      * batch    — ``execute_batch`` on a fleet of ``batch`` same-shaped
        tensors vs the per-item ``execute`` loop.
    """
    from dataclasses import replace

    from repro.core import TuckerConfig, plan, sthosvd

    cases = [((96, 64, 48), (8, 8, 8)), ((256, 24, 24), (8, 6, 6))]
    for dims, ranks in cases:
        tag = "x".join(map(str, dims))
        x = lowrank_tensor(dims, ranks, noise=0.05)
        cfg = TuckerConfig(ranks=ranks, methods="auto")
        p = plan(x.shape, x.dtype, cfg)

        t_percall = time_call(
            lambda: sthosvd(x, ranks, methods="auto"),
            reps=n_repeat)
        t_plan = time_call(
            lambda: jax.block_until_ready(p.execute(x).tucker.core),
            reps=n_repeat)
        emit(f"plan/{tag}/percall", t_percall, f"ranks={ranks}")
        emit(f"plan/{tag}/execute", t_plan,
             f"speedup=x{t_percall / t_plan:.2f};schedule={'|'.join(p.methods)}"
             f";backend={p.backend}")
        for impl in _bench_backends():
            if impl == p.backend:
                continue                      # already timed above
            pb = plan(x.shape, x.dtype, replace(cfg, impl=impl))
            t_b = time_call(
                lambda: jax.block_until_ready(pb.execute(x).tucker.core),
                reps=n_repeat)
            emit(f"plan/{tag}/execute[{impl}]", t_b,
                 f"vs_{p.backend}=x{t_plan / t_b:.2f}")

        xs = jnp.stack([lowrank_tensor(dims, ranks, noise=0.05, seed=s)
                        for s in range(batch)])
        t_loop = time_call(
            lambda: [jax.block_until_ready(p.execute(xs[b]).tucker.core)
                     for b in range(batch)], reps=2)
        t_batch = time_call(
            lambda: jax.block_until_ready(p.execute_batch(xs)[0].tucker.core),
            reps=2)
        emit(f"plan/{tag}/batch{batch}", t_batch,
             f"loop={t_loop * 1e6:.1f}us;speedup=x{t_loop / t_batch:.2f}")


def kernels_bench():
    """Per-kernel shape sweep: correctness delta + arithmetic intensity (the
    TPU-roofline characterization; wall-time on CPU interpret mode is not
    meaningful for the TPU target and is reported only as a sanity check)."""
    cases = [
        ("ttm_mode0", (512, 64, 64), 0, 32),
        ("ttm_interior", (64, 512, 64), 1, 32),
        ("ttm_last", (64, 64, 512), 2, 32),
    ]
    for name, shape, mode, r in cases:
        x = jnp.asarray(np.random.default_rng(0).standard_normal(shape), jnp.float32)
        u = jnp.asarray(np.random.default_rng(1).standard_normal((r, shape[mode])), jnp.float32)
        got = kops.ttm(x, u, mode)
        want = kref.ttm_full_ref(x, u, mode)
        err = float(jnp.abs(got - want).max())
        flops = 2 * math.prod(shape) * r
        bytes_ = 4 * (math.prod(shape) + r * shape[mode]
                      + math.prod(shape) // shape[mode] * r)
        emit(f"kernels/{name}", 0.0,
             f"maxerr={err:.2e};AI={flops / bytes_:.1f}flops_per_byte")
    # gram
    x = jnp.asarray(np.random.default_rng(2).standard_normal((128, 256, 64)), jnp.float32)
    err = float(jnp.abs(kops.gram(x, 1) - kref.gram_full_ref(x, 1)).max())
    emit("kernels/gram", 0.0, f"maxerr={err:.2e}")


def grad_compress_bench():
    """Wire bytes for each assigned arch's scanned-gradient pytree."""
    from repro import configs
    from repro.models import build
    cfg_comp = CompressionConfig(rank_fraction=0.125, max_rank=128,
                                 min_size=1 << 16, refresh_every=20)
    for arch in ("mixtral_8x22b", "gemma2_9b", "falcon_mamba_7b"):
        cfg = configs.get(arch)
        bundle = build(cfg)
        abs_params = bundle.abstract_params()
        dense = comp = 0
        for leaf in jax.tree.leaves(abs_params):
            d, c = compressed_bytes(cfg_comp, tuple(leaf.shape))
            dense += d
            comp += c
        emit(f"grad_compress/{arch}", 0.0,
             f"dense={dense/2**30:.2f}GiB;wire={comp/2**30:.2f}GiB;"
             f"ratio=x{dense/comp:.1f}")


def tiny_train_bench(steps: int = 10):
    """Measured steps/s of the end-to-end driver on the smoke config."""
    from repro import configs
    from repro.data.pipeline import DataConfig, make_source
    from repro.models import build
    from repro.models.config import ShapeConfig
    from repro.optim.adamw import AdamW
    from repro.train.train_step import init_state, make_train_step

    cfg = configs.get_smoke("phi3_mini_3p8b").with_(remat=False)
    bundle = build(cfg)
    shape = ShapeConfig("bench", seq_len=64, global_batch=8, kind="train")
    src = make_source(DataConfig(seed=0), cfg, shape)
    opt = AdamW(lr=1e-3)
    state = init_state(bundle, opt, jax.random.PRNGKey(0))
    step = make_train_step(bundle, opt)
    state, _ = step(state, src.batch_at(0))      # compile
    t0 = time.perf_counter()
    for i in range(1, steps + 1):
        state, m = step(state, src.batch_at(i))
    jax.block_until_ready(m["loss"])
    dt = (time.perf_counter() - t0) / steps
    tok_s = shape.global_batch * shape.seq_len / dt
    emit("train/tiny_steps", dt, f"tokens_per_s={tok_s:.0f}")
