"""Benchmark harness: one function per paper table/figure + system benches.
Prints ``name,us_per_call,derived`` CSV.  ``--full`` uses paper-scale dims.

After the selected benches run, every ``BENCH_*.json`` row file in the
working directory (written by the per-bench CLIs, here or in earlier CI
steps) is merged into one ``BENCH_summary.json`` — a single artifact whose
rows carry their source bench, so cross-PR perf trajectories need one
download, not eight."""

import argparse
import json
import sys
import traceback
from pathlib import Path


def merge_bench_files(out: str = "BENCH_summary.json") -> Path | None:
    """Merge cwd's ``BENCH_*.json`` docs into one summary row file (the
    same row schema ``summary_md`` reads, each row tagged with its source
    bench/platform).  Returns the written path, or None when there was
    nothing to merge."""
    paths = sorted(p for p in Path().glob("BENCH_*.json") if p.name != out)
    if not paths:
        return None
    rows, sources = [], {}
    for p in paths:
        try:
            doc = json.loads(p.read_text())
        except (OSError, json.JSONDecodeError):
            continue
        sources[p.name] = {k: v for k, v in doc.items() if k != "rows"}
        for r in doc.get("rows", []):
            rows.append({"source": doc.get("bench", p.stem), **r})
    path = Path(out)
    path.write_text(json.dumps(
        {"bench": "summary", "sources": sources, "rows": rows}, indent=1))
    return path


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale dims (hours on 1 CPU core)")
    ap.add_argument("--only", default=None, help="comma-list of bench names")
    ap.add_argument("--no-summary", action="store_true",
                    help="skip the BENCH_summary.json merge step")
    args = ap.parse_args()

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    from . import backend_bench as bb
    from . import obs_bench as obsb
    from . import order_bench as ob
    from . import paper_figs as pf
    from . import selector_bench as selb
    from . import serve_bench as svb
    from . import sketch_bench as skb
    from . import system_bench as sb

    benches = {
        "backend": lambda: bb.bench_backends(full=args.full),
        "order": lambda: ob.bench_orders(full=args.full),
        "selector_sweep": lambda: (selb.bench_sweeps(full=args.full),
                                   selb.bench_selection_overhead()),
        "fig2": lambda: pf.fig2_solver_variants(full=args.full),
        "table3": lambda: pf.table3_realworld(full=args.full),
        "fig5": lambda: pf.fig5_adaptive_speedup(),
        "fig6": lambda: pf.fig6_modewise_trace(),
        "fig7": lambda: pf.fig7_selector_overhead(),
        "fig8": lambda: pf.fig8_matfree(full=args.full),
        "selector": lambda: pf.selector_accuracy(),
        "serve": lambda: svb.bench_serve(full=args.full),
        "obs": lambda: obsb.bench_obs(full=args.full),
        "sketch": lambda: skb.bench_sketch(
            tier="full" if args.full else "default"),
        # lazy import: forces 8 virtual host devices, which only takes
        # effect if jax has not initialized yet (run with --only modepar for
        # a clean mesh; inside a full sweep it degrades to a skip message)
        "modepar": lambda: __import__(
            "benchmarks.modepar_bench", fromlist=["bench_modepar"]
        ).bench_modepar(full=args.full),
        "plan": sb.plan_bench,
        "kernels": sb.kernels_bench,
        "grad_compress": sb.grad_compress_bench,
        "tiny_train": sb.tiny_train_bench,
    }
    only = set(args.only.split(",")) if args.only else None
    print("name,us_per_call,derived")
    failed = []
    for name, fn in benches.items():
        if only and name not in only:
            continue
        try:
            fn()
        except Exception:
            failed.append(name)
            traceback.print_exc()
    if not args.no_summary:
        merged = merge_bench_files()
        if merged is not None:
            print(f"wrote {merged}")
    if failed:
        print(f"FAILED: {failed}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
