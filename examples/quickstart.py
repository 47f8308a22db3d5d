"""a-Tucker quickstart: the TuckerConfig → plan → execute front door.

    PYTHONPATH=src python examples/quickstart.py

Builds a synthetic low-rank tensor, plans a decomposition (the adaptive
selector resolves the per-mode solver schedule ONCE, ahead of time), and
executes the frozen plan — then shows what planning buys: cached compiled
sweeps for repeated executes, one vmapped program for a fleet of tensors,
and the legacy per-call baselines for comparison.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (TuckerConfig, plan, sthosvd, sthosvd_als, sthosvd_eig,
                        tensor_ops as T)


def make_tensor(dims, ranks, seed=0, noise=0.02):
    rng = np.random.default_rng(seed)
    core = rng.standard_normal(ranks)
    us = [np.linalg.qr(rng.standard_normal((d, r)))[0] for d, r in zip(dims, ranks)]
    x = T.reconstruct(jnp.asarray(core, jnp.float32),
                      [jnp.asarray(u, jnp.float32) for u in us])
    return x + noise * float(jnp.std(x)) * jnp.asarray(
        rng.standard_normal(dims), jnp.float32)


def main():
    # a deliberately asymmetric tensor (one long mode — the regime where the
    # solver choice matters; cf. the paper's Air Quality tensor)
    dims, ranks = (600, 80, 40), (10, 10, 8)
    x = make_tensor(dims, ranks)

    # 1. plan: selector + cost model run here, never in the hot path
    # impl="auto" also picks the ops backend for this platform (TPU → the
    # Pallas kernels, CPU/GPU → matfree jnp contractions)
    cfg = TuckerConfig(ranks=ranks, methods="auto", impl="auto")
    p = plan(x.shape, x.dtype, cfg)
    print(f"tensor {dims} → ranks {ranks}")
    print(f"planned schedule: {' | '.join(f'{s.mode}:{s.method}' for s in p.schedule)}"
          f"   ops backend: {p.backend}")
    print(f"modeled cost: {p.total_flops / 1e6:.1f} MFLOP, "
          f"peak working set {p.peak_bytes / 2**20:.1f} MiB\n")

    # 2. execute: first call compiles the whole sweep as ONE program …
    t0 = time.perf_counter()
    res = p.execute(x)
    jax.block_until_ready(res.tucker.core)
    compile_and_run = time.perf_counter() - t0
    # … repeated executes reuse it (zero recompiles, zero selector calls)
    t0 = time.perf_counter()
    for _ in range(5):
        res = p.execute(x)
        jax.block_until_ready(res.tucker.core)
    warm = (time.perf_counter() - t0) / 5
    tt = res.tucker
    print(f"plan.execute        first={compile_and_run * 1e3:8.1f} ms  "
          f"warm={warm * 1e3:8.1f} ms   rel_err={float(tt.rel_error(x)):.4f}"
          f"   compression=x{tt.compression_ratio:.0f}")

    # 3. batched execution: a fleet of same-shaped tensors, one vmapped program
    xs = jnp.stack([make_tensor(dims, ranks, seed=s) for s in range(4)])
    p.execute_batch(xs)                     # warm-up (compile)
    t0 = time.perf_counter()
    batch = p.execute_batch(xs)
    jax.block_until_ready(batch[0].tucker.core)
    dt = time.perf_counter() - t0
    errs = [float(r.tucker.rel_error(xi)) for r, xi in zip(batch, xs)]
    print(f"plan.execute_batch  {len(batch)} tensors in {dt * 1e3:8.1f} ms  "
          f"max_err={max(errs):.4f}")

    # 4. legacy per-call baselines (selector/dispatch inside every call)
    print()
    for name, fn in (("st-HOSVD-EIG", sthosvd_eig),
                     ("st-HOSVD-ALS", sthosvd_als),
                     ("a-Tucker per-call",
                      lambda x_, r_, **kw: sthosvd(x_, r_, methods="auto", **kw))):
        fn(x, ranks)                        # warm-up (compile)
        t0 = time.perf_counter()
        r = fn(x, ranks)
        dt = time.perf_counter() - t0
        print(f"{name:19s} {dt * 1e3:8.1f} ms   "
              f"rel_err={float(r.tucker.rel_error(x)):.4f}   "
              f"modes={'|'.join(f'{t.mode}:{t.method}' for t in sorted(r.trace, key=lambda t: t.mode))}")

    # 5. error-targeted decomposition: no ranks — ask for an accuracy and
    # let the plan's rank policy read per-mode ranks off a randomized
    # sketch of the input (then refine with the usual eig/als sweep)
    eps = 0.05
    acfg = TuckerConfig(error_target=eps)
    ap_ = plan(x.shape, x.dtype, acfg)
    ares = ap_.execute(x)
    aerr = float(ares.tucker.rel_error(x))
    print(f"\nerror_target={eps}   chose ranks {ares.tucker.ranks}   "
          f"rel_err={aerr:.4f}   certified bound={ares.error_bound:.4f}")
    assert aerr <= eps, f"achieved error {aerr} exceeds target {eps}"
    assert ares.error_bound <= eps

    # 6. plans are JSON — ship a schedule tuned on one box to another
    blob = p.to_json()
    print(f"\nplan serializes to {len(blob)} bytes of JSON "
          f"(TuckerPlan.save / TuckerPlan.load)")


if __name__ == "__main__":
    main()
