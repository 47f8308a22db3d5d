"""The benchmark's harness: one cell, one seed, one run.

Everything that belongs to one configuration, traffic mix or metric is a
file of its own, found by the name ``BENCHMARK.json`` gives it:

  configs/<config>.json     sizes, data model and stated guarantees
  traffic/<traffic>.json    parameters of the general load generator below
  checks/<workload>.json    the limit of each number that decides correct
  metrics/<metric>.py       ``read(ctx) -> float | None`` for one metric

A run makes its inputs on the device from the seed, warms up every program
the window meets, drives the program for the window (closed loop: one
caller, ``repro.core.decompose``; open loop: scheduled arrivals through one
``repro.serve.TuckerService``), and then, with the program's work done,
compares what the window produced with the plain reference
(``bench_reference``).
"""

from __future__ import annotations

import contextlib
import json
import math
import shutil
import sys
import tempfile
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from importlib import util as _imputil
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parents[1]
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
#: waiter threads for open-loop completions; more outstanding requests than
#: this would stamp some completions late
WAITERS = 256


@dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    checks: dict
    chips: int
    metrics: dict = field(default_factory=dict)   # name -> manifest entry
    per_layer: list = field(default_factory=list)
    end_to_end: list = field(default_factory=list)


def _applies(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(known: {sorted(cells)})")
    w = cells[workload]
    cfg = {c["name"]: c for c in manifest["configs"]}[w["config"]]
    e2e = [m for m in manifest["end_to_end"] if _applies(m, workload)]
    layer = [m for m in manifest["per_layer"] if _applies(m, workload)]
    return Cell(
        name=workload,
        config=json.loads((root / cfg["file"]).read_text()),
        traffic=json.loads((BENCH / "traffic" / f"{w['traffic']}.json")
                           .read_text()),
        checks=json.loads((BENCH / "checks" / f"{workload}.json").read_text()),
        chips=int(w["chips"]),
        metrics={m["name"]: m for m in e2e + layer},
        per_layer=[m["name"] for m in layer],
        end_to_end=[m["name"] for m in e2e])


def request_of(cell: Cell) -> dict:
    """The traffic's request with ``"published"`` ranks resolved."""
    req = dict(cell.traffic["request"])
    if req.get("ranks") == "published":
        req["ranks"] = list(cell.config["ranks"])
    return req


def read_metric(name: str, ctx: dict):
    path = BENCH / "metrics" / f"{name}.py"
    spec = _imputil.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = _imputil.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def program():
    """The system under test, imported from the checkout's ``src``."""
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    import repro.core
    import repro.serve
    return repro


# -- inputs ------------------------------------------------------------------

def _shape_with(shape, axis, extent):
    s = list(shape)
    s[axis] = int(extent)
    return tuple(s)


def make_inputs(cell: Cell, seed: int) -> dict:
    """``{input id: tensor}``: the closed loop's pool, or for an open loop
    ``pool_per_extent`` tensors of each extent."""
    from bench_data import key_for, lowrank
    cfg, tr = cell.config, cell.traffic
    noise = cfg["assumed"]["noise"]
    if tr["loop"] == "closed":
        return {i: lowrank(key_for(seed, i), cfg["shape"], cfg["ranks"], noise)
                for i in range(tr["pool"])}
    ext = tr["extents"]
    return {(v, j): lowrank(key_for(seed, vi, j),
                            _shape_with(cfg["shape"], ext["axis"], v),
                            cfg["ranks"], noise)
            for vi, v in enumerate(ext["values"])
            for j in range(tr["pool_per_extent"])}


def arrival_schedule(traffic: dict, seed: int, seconds: float,
                     rate: float | None = None) -> list:
    """``[(offset_s, input id), ...]`` for an open loop.  Every seed gets
    the same multiset of inter-arrival gaps (the exponential distribution's
    quantiles at the stated rate) and of extents (their stated shares), in
    a seeded order, so seeds change the order of the work and not its
    amount."""
    rate = float(traffic["rate_per_s"] if rate is None else rate)
    rng = np.random.default_rng(seed)
    n = max(1, round(rate * seconds))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    rng.shuffle(gaps)
    starts = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    starts *= seconds / float(np.sum(gaps))
    ext = traffic["extents"]
    w = np.asarray(ext["weights"], float) / float(np.sum(ext["weights"]))
    counts = np.floor(w * n).astype(int)
    for i in np.argsort(-(w * n - counts))[:n - counts.sum()]:
        counts[i] += 1
    extents = np.repeat(np.asarray(ext["values"]), counts)
    rng.shuffle(extents)
    pool = rng.integers(traffic["pool_per_extent"], size=n)
    return [(float(t), (int(v), int(j)))
            for t, v, j in zip(starts, extents, pool)]


# -- the window --------------------------------------------------------------

@dataclass
class Window:
    """What one measured window produced."""
    seconds: float = 0.0             # host-clock length of the window
    attempted: int = 0
    failed: int = 0
    missing: int = 0                 # sampled answers that never came
    calls: list = field(default_factory=list)     # completed: shape, ranks
    latencies_s: list = field(default_factory=list)
    lateness_s: list = field(default_factory=list)
    #: open loop: (seconds inside ``submit``, due offset s) per request
    admission_s: list = field(default_factory=list)
    #: open loop: device bytes in use, read every 50th arrival
    in_use: list = field(default_factory=list)
    sampled: list = field(default_factory=list)   # (input id, result)
    counters: dict | None = None
    #: open loop: (due offset s, latency s or None) per request
    timeline: list = field(default_factory=list)


def _result(res):
    return {"core": res.tucker.core, "factors": list(res.tucker.factors),
            "error_bound": res.error_bound}


def closed_window(call, inputs: dict, seconds: float, seed: int,
                  sample: int) -> Window:
    """One caller, each call waiting for the last: inputs in turn."""
    import jax
    ids = list(inputs)
    win, done = Window(), []
    t0 = time.perf_counter()
    i = 0
    while time.perf_counter() - t0 < seconds:
        k = ids[i % len(ids)]
        i += 1
        win.attempted += 1
        t1 = time.perf_counter()
        try:
            res = call(inputs[k])
            jax.block_until_ready((res.tucker.core, res.tucker.factors))
        except Exception as e:  # noqa: BLE001 - a failed call is counted
            print(f"call failed: {type(e).__name__}: {e}", file=sys.stderr)
            win.failed += 1
            continue
        win.latencies_s.append(time.perf_counter() - t1)
        win.calls.append({"shape": tuple(inputs[k].shape),
                          "ranks": tuple(res.tucker.core.shape)})
        done.append((k, _result(res)))
    win.seconds = time.perf_counter() - t0
    rng = np.random.default_rng(seed)
    keep = sorted(rng.choice(len(done), size=min(sample, len(done)),
                             replace=False)) if done else []
    win.sampled = [done[j] for j in keep]
    return win


def service_counters(svc) -> dict:
    """Sum of the service's per-bucket counters."""
    tot: dict[str, int] = {}
    for bs in list(svc._buckets.values()):
        for f in ("waves", "lanes", "lanes_filled", "true_elems",
                  "slot_elems", "completed", "padded", "failed"):
            tot[f] = tot.get(f, 0) + int(getattr(bs.metrics, f))
    return tot


def open_window(svc, config, inputs: dict, schedule: list, seconds: float,
                seed: int, sample: int, drain_s: float) -> Window:
    """Scheduled arrivals through ``svc`` (already started).  Each request
    is timed from when it was due to when its result was ready; requests
    due in the window are waited for until ``drain_s`` past its end."""
    n = len(schedule)
    rng = np.random.default_rng(seed + 1)
    keep = set(rng.choice(n, size=min(sample, n), replace=False).tolist())
    longest = max(range(n), key=lambda k: math.prod(inputs[schedule[k][1]]
                                                   .shape))
    if longest not in keep:
        keep.discard(max(keep))
        keep.add(longest)
    recs = [dict(due=0.0, done=None, error=None, result=None)
            for _ in range(n)]
    win = Window(attempted=n)
    before = service_counters(svc)
    t0 = time.perf_counter()
    deadline = t0 + seconds + drain_s

    def await_one(k, ticket):
        try:
            res = svc.wait(ticket, timeout=max(0.0, deadline -
                                               time.perf_counter()))
            recs[k]["done"] = time.perf_counter()
            if k in keep:
                recs[k]["result"] = _result(res)
            recs[k]["ranks"] = tuple(res.tucker.core.shape)
        except TimeoutError:
            pass
        except Exception as e:  # noqa: BLE001 - a failed request is counted
            recs[k]["error"] = f"{type(e).__name__}: {e}"

    with ThreadPoolExecutor(max_workers=WAITERS) as pool:
        for k, (off, key) in enumerate(schedule):
            due = t0 + off
            delay = due - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            recs[k]["due"] = due
            win.lateness_s.append(max(0.0, time.perf_counter() - due))
            if k % 50 == 0:
                win.in_use.append(_memory_in_use())
            t_sub = time.perf_counter()
            try:
                ticket = svc.submit(inputs[key], config)
            except Exception as e:  # noqa: BLE001 - a refused request counts
                recs[k]["error"] = f"{type(e).__name__}: {e}"
                continue
            finally:
                win.admission_s.append((time.perf_counter() - t_sub, off))
            pool.submit(await_one, k, ticket)
    win.seconds = seconds
    after = service_counters(svc)
    win.counters = {f: after[f] - before.get(f, 0) for f in after}
    for k, r in enumerate(recs):
        key = schedule[k][1]
        win.timeline.append((schedule[k][0], None if r["done"] is None
                             else r["done"] - r["due"]))
        if r["done"] is not None:
            win.latencies_s.append(r["done"] - r["due"])
            win.calls.append({"shape": tuple(inputs[key].shape),
                              "ranks": r["ranks"]})
            if k in keep:
                win.sampled.append((key, r["result"]))
        else:
            win.failed += 1
            win.latencies_s.append(math.inf)
            if r["error"] is None and k in keep:
                win.missing += 1
            if r["error"] is not None:
                print(f"request {k} failed: {r['error']}", file=sys.stderr)
    return win


# -- set-up per loop ---------------------------------------------------------

def tucker_config(cell: Cell, repro):
    """The cell's request as the ``TuckerConfig`` a user would pass."""
    return repro.core.TuckerConfig(
        **{k: tuple(v) if isinstance(v, list) else v
           for k, v in request_of(cell).items()})


class ClosedLoop:
    def __init__(self, cell: Cell, seed: int, repro):
        self.cell, self.seed = cell, seed
        self.inputs = make_inputs(cell, seed)
        cfg = tucker_config(cell, repro)
        self.call = lambda x: repro.core.decompose(x, cfg)

    def warm_up(self):
        import jax
        for x in self.inputs.values():
            res = self.call(x)
            jax.block_until_ready((res.tucker.core, res.tucker.factors))

    def window(self, seconds: float) -> Window:
        return closed_window(self.call, self.inputs, seconds, self.seed,
                             self.cell.traffic["check_sample"])

    def close(self):
        pass


class OpenLoop:
    def __init__(self, cell: Cell, seed: int, repro):
        self.cell, self.seed = cell, seed
        self.inputs = make_inputs(cell, seed)
        self.config = tucker_config(cell, repro)
        self.svc = repro.serve.TuckerService()
        self.slots = repro.serve.BucketPolicy().wave_slots

    def warm_up(self):
        """Every extent at every wave size, synchronously, so each wave
        holds exactly that many requests; then the worker starts.  The
        8-wide waves of the longest extent set the process's peak device
        memory; what stays in use after them is printed."""
        per = self.cell.traffic["pool_per_extent"]
        for v in self.cell.traffic["extents"]["values"]:
            for k in range(1, self.slots + 1):
                tickets = [self.svc.submit(self.inputs[(v, j % per)],
                                           self.config) for j in range(k)]
                self.svc.drain()
                for t in tickets:
                    self.svc.wait(t)
        print(f"device memory after warm-up: {_memory_in_use()} bytes in "
              f"use", file=sys.stderr)
        self.svc.start()

    def window(self, seconds: float, rate: float | None = None) -> Window:
        tr = self.cell.traffic
        schedule = arrival_schedule(tr, self.seed, seconds, rate)
        return open_window(self.svc, self.config, self.inputs, schedule,
                           seconds, self.seed, tr["check_sample"],
                           tr["drain_s"])

    def close(self):
        self.svc.close()


LOOPS = {"closed": ClosedLoop, "open": OpenLoop}


# -- correctness -------------------------------------------------------------

def compare(inputs: dict, request: dict, sampled: list,
            answers: list | None = None, refs: dict | None = None) -> dict:
    """The numbers that decide ``correct``, over the sampled answers:

    ``subspace``    largest ``||U U^T - U_ref U_ref^T||_2`` over answers
                    and modes;
    ``core_resid``  largest ``||core - X x_n U_n^T|| / ||X||``;
    ``ranks``       answers whose ranks differ from the reference's
                    (rank-adaptive requests);
    ``rel_error``   largest relative error of an answer (rank-adaptive
                    requests, against their error target);
    ``error_bound`` largest error bound an answer reports (rank-adaptive
                    requests, against their error target);
    ``bound_excess`` largest amount by which an answer's relative error
                    exceeds the bound it reports, a missing bound at
                    infinity (rank-adaptive requests).

    ``answers`` replaces the sampled results, index for index (the
    control); the reference is computed once per input (``refs`` carries
    them between calls).  ``subspace_modes`` gives the largest subspace
    gap of each mode."""
    import bench_reference as ref
    refs = {} if refs is None else refs
    out = {"subspace": 0.0, "core_resid": 0.0}
    by_mode: list[float] = []
    adaptive = request.get("error_target") is not None
    if adaptive:
        out.update(ranks=0, rel_error=0.0, error_bound=0.0,
                   bound_excess=-math.inf)
    for i, (key, res) in enumerate(sampled):
        x = inputs[key]
        if answers is not None:
            res = answers[i]
        if key not in refs:
            if adaptive:
                core, us, ranks, _ = ref.sthosvd_adaptive(
                    x, request["error_target"])
            else:
                core, us = ref.sthosvd(x, request["ranks"])
                ranks = tuple(request["ranks"])
            refs[key] = (us, ranks)
        us, ranks = refs[key]
        core, factors = res["core"], res["factors"]
        for m, (u, u_ref) in enumerate(zip(factors, us)):
            gap = ref.subspace_gap(u, u_ref)
            if m == len(by_mode):
                by_mode.append(0.0)
            by_mode[m] = max(by_mode[m], gap)
            out["subspace"] = max(out["subspace"], gap)
        out["core_resid"] = max(out["core_resid"],
                                ref.core_residual(x, core, factors))
        if adaptive:
            out["ranks"] += tuple(core.shape) != tuple(ranks)
            err = ref.rel_error(x, core, factors)
            bound = res["error_bound"]
            bound = math.inf if bound is None else float(bound)
            out["rel_error"] = max(out["rel_error"], err)
            out["error_bound"] = max(out["error_bound"], bound)
            out["bound_excess"] = max(out["bound_excess"], err - bound)
    out["subspace_modes"] = by_mode
    return out


def control_answers(inputs: dict, request: dict, sampled: list,
                    precision: str = "bf16") -> list:
    """The reference one precision step down, in the program's place."""
    import bench_reference as ref
    memo: dict = {}
    out = []
    for key, _ in sampled:
        if key not in memo:
            core, us, bound = ref.decompose(inputs[key], request, precision)
            memo[key] = {"core": core, "factors": us, "error_bound": bound}
        out.append(memo[key])
    return out


def judge(numbers: dict, checks: dict, missing: int) -> tuple[bool, dict]:
    """``correct`` and ``{name: {"value", "limit"}}`` for every number the
    cell's checks file limits."""
    shown = {k: {"value": numbers[k], "limit": c["limit"]}
             for k, c in checks.items() if k in numbers}
    ok = missing == 0 and len(shown) == len(checks) and all(
        v["value"] <= v["limit"] for v in shown.values())
    for v in shown.values():   # JSON has no infinities
        if not math.isfinite(v["value"]):
            v["value"] = repr(float(v["value"]))
    if missing:
        shown["missing"] = {"value": missing, "limit": 0}
    return ok, shown


# -- one run -----------------------------------------------------------------

class _CompileCounter:
    def __init__(self):
        self.on = False
        self.count = 0

    def __call__(self, event, duration, **kw):
        if self.on and event == BACKEND_COMPILE:
            self.count += 1


def _memory_in_use() -> int:
    """Device memory in use on the fullest chip, in bytes."""
    import jax
    return max(int((d.memory_stats() or {}).get("bytes_in_use", 0))
               for d in jax.local_devices())


def _memory_peak() -> int:
    """The process's peak device memory on the fullest chip, in bytes."""
    import jax
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.local_devices())


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, peaks: dict | None) -> dict:
    """Drive one run of ``cell`` and return its result line (without
    printing).  ``t_start`` is when the process began its set-up."""
    import jax
    repro = program()
    loop = LOOPS[cell.traffic["loop"]](cell, seed, repro)
    # a traced window runs the program with its spans on, which takes
    # paths of its own (span attributes, a donation probe): warm those too
    with (repro.obs.capture() if trace else contextlib.nullcontext()):
        loop.warm_up()
    setup_peak = _memory_peak()
    counter = _CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter)
    setup_s = time.perf_counter() - t_start
    dtrace = events = None
    tmp = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        counter.on = True
        if trace:
            import bench_trace
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(tmp, profiler_options=opts)
            try:
                with repro.obs.capture(maxlen=1 << 20) as buf, \
                        jax.profiler.TraceAnnotation(bench_trace.WINDOW):
                    win = loop.window(seconds)
            finally:
                jax.profiler.stop_trace()
            counter.on = False
            events = buf.events()
            dtrace = bench_trace.read_xplane(tmp)
        else:
            win = loop.window(seconds)
        counter.on = False
    finally:
        jax.monitoring.unregister_event_duration_listener(counter)
        loop.close()
        if tmp:
            shutil.rmtree(tmp, ignore_errors=True)
    memory_peak = _memory_peak()
    limit = (jax.devices()[0].memory_stats() or {}).get("bytes_limit")
    print(f"window: {win.seconds:.3f} s, {len(win.calls)} completed of "
          f"{win.attempted}, {win.failed} failed; compiles in window: "
          f"{counter.count}", file=sys.stderr)
    print(f"device memory peak: {setup_peak} bytes after set-up, "
          f"{memory_peak} after the window, of {limit}; in use after the "
          f"window: {_memory_in_use()}", file=sys.stderr)
    if win.lateness_s:
        late = np.asarray(win.lateness_s)
        print(f"generator lateness: mean {late.mean() * 1e3:.3f} ms, p99 "
              f"{np.percentile(late, 99) * 1e3:.3f} ms, max "
              f"{late.max() * 1e3:.3f} ms", file=sys.stderr)
    if win.admission_s:
        t_max, at = max(win.admission_s)
        print(f"longest submit: {t_max * 1e3:.3f} ms, due at "
              f"{at:.3f} s", file=sys.stderr)
        from bench_metrics import latency_percentile_ms
        q = {p: latency_percentile_ms({"latencies_s": win.latencies_s}, p)
             for p in (90, 99)}
        print(f"latency p90 {q[90]!r} ms, p99 {q[99]!r} ms; device memory "
              f"in use at arrivals: max {max(win.in_use)}, last "
              f"{win.in_use[-1]}", file=sys.stderr)

    ctx = {"cell": cell, "peaks": peaks, "setup_s": setup_s,
           "window_s": win.seconds, "completed": len(win.calls),
           "calls": win.calls, "latencies_s": win.latencies_s,
           "counters": win.counters, "trace": dtrace, "obs_events": events}
    names = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for name in names:
        v = read_metric(name, ctx)
        if v is not None:
            metrics[name] = {"value": float(v),
                             "unit": cell.metrics[name]["unit"]}

    t_check = time.perf_counter()
    numbers = compare(loop.inputs, request_of(cell), win.sampled)
    correct, checks = judge(numbers, cell.checks, win.missing)
    print(f"compared {len(win.sampled)} answers with the reference in "
          f"{time.perf_counter() - t_check:.3f} s", file=sys.stderr)
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": memory_peak}
    out = {"correct": bool(correct and win.attempted > 0),
           "attempted": win.attempted, "failed": win.failed,
           "metrics": metrics, "device": device}
    if dtrace is not None:
        device["busy_s"] = dtrace.busy_s()
        device["window_s"] = dtrace.window_s
        out["breakdown"] = {"device_ops": dtrace.top_ops(),
                            "idle_gaps": dtrace.idle_gaps()}
    out["checks"] = checks
    return out
