#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip.

    python3 benchmarks/chip/run.py --workload cavity.target --seed 7 \
        --seconds 51 --trace 0

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics, read from a profiler trace of the
window), ``device``, with ``--trace 1`` a ``breakdown``, and last
``checks``: each number compared with the reference beside its limit.  The
same checks are the last lines of standard error.

Exits 2 without a result when JAX finds no TPU or fewer chips than the
cell asks for.  JAX's persistent compilation cache is kept in ``.jax_cache``
at the root of the checkout, for every program, however quickly it
compiles, so only a checkout's first run compiles.  The TPU runtime's own
logs are off unless ``TPU_LOG_DIR`` says where to write them, so a run
writes nothing outside its checkout.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench_harness import ROOT, load_cell, run_cell
    from bench_roofline import peaks_for
    cell = load_cell(args.workload)

    import jax
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"run.py: needs a TPU, JAX found {devices[0].platform!r}",
              file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"run.py: {cell.name} needs {cell.chips} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    peaks = peaks_for(devices[0].device_kind)

    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), T_START,
                   peaks)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
