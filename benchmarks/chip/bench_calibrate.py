#!/usr/bin/env python3
"""Readings that the benchmark's limits and rates are set from, on the chip.

    python3 benchmarks/chip/bench_calibrate.py --workload cavity.target \
        --seeds 11,12,13 --seconds 3
    python3 benchmarks/chip/bench_calibrate.py --workload cavity.serve \
        --seeds 5 --seconds 20 --rates 40,60,80

Without ``--rates``, for each seed in one process: the cell's inputs, a
short window of the program at the cell's own size and load, and the
numbers ``correct`` compares, for the program's answers and for the
control's (the reference in one bfloat16 pass, on the same inputs).
With ``--rates`` (open loops), one seed's set-up and then a window at
each offered rate: completions, latency quantiles, and whether the
backlog grew (the latency of the window's last quarter against its
first).  One JSON line per reading.  The benchmark's own runs never run
this.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

os.environ.setdefault("TPU_LOG_DIR", "disabled")


def _q(xs, q):
    xs = [x for x in xs if x is not None]
    return float(np.percentile(xs, q)) * 1e3 if xs else None


def sweep(loop, seconds, rates):
    for rate in rates:
        win = loop.window(seconds, rate)
        tl = win.timeline
        n = len(tl)
        first = [lat for off, lat in tl[: n // 4]]
        last = [lat for off, lat in tl[-(n // 4):]]
        done_in_window = sum(1 for off, lat in tl
                             if lat is not None and off + lat <= seconds)
        print(json.dumps({
            "rate_per_s": rate, "attempted": win.attempted,
            "failed": win.failed,
            "completed_by_window_end": done_in_window,
            "p50_ms": _q([lat for _, lat in tl], 50),
            "p95_ms": _q([lat for _, lat in tl], 95),
            "first_quarter_p95_ms": _q(first, 95),
            "last_quarter_p95_ms": _q(last, 95),
            "lateness_max_ms": max(win.lateness_s) * 1e3,
            "counters": win.counters}), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", default="")
    args = ap.parse_args(argv)

    import bench_harness as H
    cell = H.load_cell(args.workload)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(H.ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"needs a TPU, JAX found {dev.platform!r}", file=sys.stderr)
        return 2
    repro = H.program()
    request = H.request_of(cell)
    seeds = [int(s) for s in args.seeds.split(",")]
    rates = [float(r) for r in args.rates.split(",") if r]
    for seed in seeds:
        t0 = time.perf_counter()
        loop = H.LOOPS[cell.traffic["loop"]](cell, seed, repro)
        loop.warm_up()
        setup = time.perf_counter() - t0
        if rates:
            sweep(loop, args.seconds, rates)
            loop.close()
            return 0
        win = loop.window(args.seconds)
        loop.close()
        line = {"seed": seed, "setup_s": setup, "window_s": win.seconds,
                "completed": len(win.calls), "failed": win.failed,
                "compared": len(win.sampled),
                "ranks": sorted({c["ranks"] for c in win.calls})}
        refs: dict = {}
        line["program"] = H.compare(loop.inputs, request, win.sampled,
                                    refs=refs)
        answers = H.control_answers(loop.inputs, request, win.sampled)
        line["control"] = H.compare(loop.inputs, request, win.sampled,
                                    answers=answers, refs=refs)
        print(json.dumps(line), flush=True)
        del loop, win
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
