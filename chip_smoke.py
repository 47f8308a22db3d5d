#!/usr/bin/env python3
"""Smoke run of the a-Tucker service on a TPU, at the paper's tensor sizes.

    python3 chip_smoke.py [--seed N]        # one chip
    python3 chip_smoke.py --four-chips      # the sharded path on four chips

Drives the public entry points a user calls — ``plan`` → ``execute`` and
``TuckerService`` — on shape-faithful low-rank-plus-noise tensors of the
paper's Table III, made on the device from ``--seed``:

  fixed     Cavity (100×100×10000, ranks 20) and Boats (320×240×7000, ranks
            10), each with the default backend (``matfree``) and with
            ``impl="auto"``, which must resolve to the Pallas kernels; Cavity
            again with ``mode_order="opt"``.
  target    Cavity with ``error_target=0.1``.
  serve     one ``TuckerService`` answering 8 MNIST (784×5000×10) and Video
            (112×160×3×32) requests at their Table III ranks.
  small     the same plan path against an independent float64 NumPy
            st-HOSVD on a small tensor.

Every result is checked: finite, orthonormal factors, and a relative error
within ``TOL_REL_ERR`` of the float32 ``matfree`` result (``Precision.
HIGHEST``) of the same tensor on the same chip.  ``--four-chips`` runs only a
sharded plan of Boats on a 4-device mesh, sequential and mode-parallel,
against the one-chip ``matfree`` result computed in the same process.

Each phase prints one JSON line.  The last line is ``{"ok": true, "device":
{...}}`` only when every phase passed with no fallback hop; otherwise the
script exits nonzero.  It refuses to run without a TPU.  One process holds
the chip and starts no other.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

#: |rel_error − rel_error of the float32 HIGHEST matfree result|
TOL_REL_ERR = 2e-3
#: max |UᵀU − I| over every factor
TOL_ORTHO = 1e-4
#: relative Frobenius norm of the noise added to each low-rank tensor
NOISE = 0.05

#: (shape, ranks) from the paper's Table III
CAVITY = ((100, 100, 10000), (20, 20, 20))
BOATS = ((320, 240, 7000), (10, 10, 10))
MNIST = ((784, 5000, 10), (65, 142, 10))
VIDEO = ((112, 160, 3, 32), (10, 10, 3, 32))
SMALL = ((40, 30, 20), (6, 5, 4))


class SmokeFailure(RuntimeError):
    """A phase's result missed a stated check."""


def emit(**fields) -> None:
    print(json.dumps(fields, default=str), flush=True)


def lowrank(key, shape, ranks):
    """Core ×_n U_n with orthonormal U_n, plus NOISE-relative Gaussian
    noise — generated on the device."""
    import jax
    import jax.numpy as jnp

    from repro.core import tensor_ops as T

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(shape) + 2)
        core = jax.random.normal(keys[0], ranks, jnp.float32)
        us = [jnp.linalg.qr(jax.random.normal(k, (d, r), jnp.float32))[0]
              for k, d, r in zip(keys[1:], shape, ranks)]
        x = T.reconstruct(core, us)
        e = jax.random.normal(keys[-1], shape, jnp.float32)
        return x + (NOISE * T.fro_norm(x) / T.fro_norm(e)) * e

    return jax.block_until_ready(make(key))


def measure(x, res, ref_err: float | None = None) -> dict:
    """Finite, orthonormal, and (given ``ref_err``) within TOL_REL_ERR of
    the reference relative error; raises SmokeFailure otherwise."""
    import jax.numpy as jnp

    from repro.core import tensor_ops as T
    core, factors = res.tucker.core, res.tucker.factors
    if not all(bool(jnp.all(jnp.isfinite(a))) for a in (core, *factors)):
        raise SmokeFailure("non-finite core or factor")
    ortho = max(float(jnp.max(jnp.abs(
        jnp.dot(u.T, u, precision="highest") - jnp.eye(u.shape[1]))))
        for u in factors)
    err = float(T.rel_error(x, core, factors))
    out = {"rel_error": err, "ortho": ortho, "ranks": list(core.shape)}
    if ortho > TOL_ORTHO:
        raise SmokeFailure(f"factor orthonormality {ortho:.3g} > {TOL_ORTHO}")
    if ref_err is not None:
        out["d_rel_error"] = err - ref_err
        if abs(err - ref_err) > TOL_REL_ERR:
            raise SmokeFailure(f"rel_error {err:.6g} vs reference "
                               f"{ref_err:.6g}: beyond {TOL_REL_ERR}")
    return out


def timed_execute(p, x):
    """(result, cold seconds incl. compile, warm seconds)."""
    import jax
    t0 = time.perf_counter()
    res = p.execute(x)
    jax.block_until_ready((res.tucker.core, res.tucker.factors))
    cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    res = p.execute(x)
    jax.block_until_ready((res.tucker.core, res.tucker.factors))
    return res, cold, time.perf_counter() - t0


def peak_bytes() -> int | None:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def run_plan(phase: str, x, cfg, ref_err=None, expect_backend=None) -> dict:
    from repro.core import plan
    p = plan(x.shape, x.dtype, cfg)
    if expect_backend is not None and p.backend != expect_backend:
        raise SmokeFailure(f"{phase}: impl={cfg.impl!r} resolved to "
                           f"{p.backend!r}, not {expect_backend!r}")
    if p.backend == "pallas":
        assert_compiled_kernels(p, x)
    res, cold, warm = timed_execute(p, x)
    out = dict(phase=phase, shape=list(x.shape), backend=p.backend,
               solvers=list(p.methods),
               order=[s.mode for s in p.schedule], cold_s=cold, warm_s=warm,
               plan_peak_bytes=p.peak_bytes, peak_bytes_in_use=peak_bytes())
    out.update(measure(x, res, ref_err))
    if res.error_bound is not None:
        out["error_bound"] = res.error_bound
    emit(**out)
    return out


def assert_compiled_kernels(p, x) -> None:
    """A ``pallas`` plan's sweep lowers to Mosaic kernels
    (``tpu_custom_call``), not to the Pallas interpreter."""
    from repro.core.api import _make_sweep
    from repro.kernels import ops
    if ops._default_interpret():
        raise SmokeFailure("Pallas kernels would run in interpret mode")
    text = _make_sweep(p, batched=False).lower(x).as_text()
    if "tpu_custom_call" not in text:
        raise SmokeFailure("the pallas sweep holds no compiled kernel")


def reference_sthosvd(x, ranks):
    """float64 NumPy st-HOSVD by SVD of each unfolding — independent of
    the code under test.  Returns the relative error."""
    import numpy as np
    y = np.asarray(x, np.float64)
    factors = []
    for n, r in enumerate(ranks):
        unf = np.moveaxis(y, n, 0).reshape(y.shape[n], -1)
        u = np.linalg.svd(unf, full_matrices=False)[0][:, :r]
        factors.append(u)
        y = np.moveaxis(np.tensordot(u.T, np.moveaxis(y, n, 0), 1), 0, n)
    rec = y
    for n, u in enumerate(factors):
        rec = np.moveaxis(np.tensordot(u, np.moveaxis(rec, n, 0), 1), 0, n)
    xs = np.asarray(x, np.float64)
    return float(np.linalg.norm(xs - rec) / np.linalg.norm(xs))


def one_chip_phases(key) -> None:
    import jax

    from repro.core import TuckerConfig
    keys = jax.random.split(key, 8)

    shape, ranks = SMALL
    x = lowrank(keys[0], shape, ranks)
    run_plan("small", x, TuckerConfig(ranks=ranks),
             ref_err=reference_sthosvd(x, ranks))

    for k, (name, (shape, ranks)) in zip(keys[1:], (("cavity", CAVITY),
                                                    ("boats", BOATS))):
        x = lowrank(k, shape, ranks)
        ref = run_plan(f"{name}/matfree", x, TuckerConfig(ranks=ranks),
                       expect_backend="matfree")
        run_plan(f"{name}/auto", x, TuckerConfig(ranks=ranks, impl="auto"),
                 ref_err=ref["rel_error"], expect_backend="pallas")
        if name == "cavity":
            run_plan("cavity/opt", x,
                     TuckerConfig(ranks=ranks, mode_order="opt"),
                     ref_err=ref["rel_error"])
            out = run_plan("cavity/target", x,
                           TuckerConfig(error_target=0.1))
            if out["error_bound"] > 0.1 or out["rel_error"] > 0.1:
                raise SmokeFailure(f"error target 0.1 missed: bound "
                                   f"{out['error_bound']}, achieved "
                                   f"{out['rel_error']}")
        del x

    serve(keys[3:7])


def serve(keys) -> None:
    """8 mixed MNIST/Video requests through one TuckerService, twice: the
    first round compiles, the second runs warm."""
    import jax

    from repro.core import TuckerConfig, plan
    from repro.serve import TuckerService
    reqs = []
    for i, k in enumerate(keys):
        for shape, ranks in (MNIST, VIDEO):
            reqs.append((lowrank(jax.random.fold_in(k, i), shape, ranks),
                         TuckerConfig(ranks=ranks)))
    refs = [float(measure(x, plan(x.shape, x.dtype, cfg).execute(x))
                  ["rel_error"]) for x, cfg in reqs]
    svc = TuckerService()
    for rnd in ("cold", "warm"):
        t0 = time.perf_counter()
        tickets = [svc.submit(x, cfg) for x, cfg in reqs]
        results = [svc.wait(t) for t in tickets]
        jax.block_until_ready([r.tucker.core for r in results])
        wall = time.perf_counter() - t0
        if any(r is None for r in results):
            raise SmokeFailure("a served ticket came back without a result")
        checks = [measure(x, r, ref) for (x, _), r, ref
                  in zip(reqs, results, refs)]
        stats = svc.stats()
        if stats["failed"]:
            raise SmokeFailure(f"{stats['failed']} served requests failed")
        emit(phase=f"serve/{rnd}", requests=len(reqs), wall_s=wall,
             max_d_rel_error=max(abs(c["d_rel_error"]) for c in checks),
             max_ortho=max(c["ortho"] for c in checks),
             batches=stats["batches"], backends=stats["backends"],
             solvers=stats["solvers"], peak_bytes_in_use=peak_bytes())
    svc.close()


def four_chip_phase(key) -> None:
    import jax

    from repro.core import TuckerConfig
    shape, ranks = BOATS
    x = lowrank(key, shape, ranks)
    ref = run_plan("boats/one-chip", x, TuckerConfig(ranks=ranks),
                   expect_backend="matfree")
    mesh = jax.make_mesh((4,), ("data",))
    for mp in ("off", "auto"):
        run_plan(f"boats/sharded/mode_parallel={mp}", x,
                 TuckerConfig(ranks=ranks, impl="sharded", mesh=mesh,
                              mode_parallel=mp),
                 ref_err=ref["rel_error"], expect_backend="sharded")


def fallback_hops(events) -> int:
    from repro.obs import metrics
    counted = metrics.REGISTRY.counter("atucker_fallback_hops_total")
    return int(sum(counted.series().values())) + \
        sum(1 for e in events if e.get("kind") == "fallback")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded Boats phase on 4 chips")
    args = ap.parse_args()

    import jax

    from repro import obs
    from repro.compile_cache import enable_compile_cache
    from repro.core.api import donation_supported

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    want = 4 if args.four_chips else 1
    if len(jax.devices()) < want:
        print(f"chip_smoke: needs {want} chips, found {len(jax.devices())}",
              file=sys.stderr)
        return 2
    emit(phase="setup", device_kind=dev.device_kind,
         devices=len(jax.devices()), compile_cache=enable_compile_cache(),
         donation_supported=donation_supported("tpu"))
    key = jax.random.key(args.seed)
    with obs.capture() as buf:
        try:
            if args.four_chips:
                four_chip_phase(key)
            else:
                one_chip_phases(key)
        except Exception as e:  # noqa: BLE001 - report, then fail the run
            emit(phase="failed", error=f"{type(e).__name__}: {e}")
            return 1
    hops = fallback_hops(buf.events())
    emit(phase="fallbacks", hops=hops)
    if hops:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
