"""What decides ``correct``, checked on the CPU at a size a test run holds:
a sound run of each cell is correct, the control (the reference in one
bfloat16 pass, in the program's place) is not, and neither is a run whose
answers are altered where the program produces them.  The harness's look
for a chip is checked too: without a TPU the command prints no result."""

from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import bench_harness as H  # noqa: E402

CELLS = ["cavity.serve", "cavity.target"]
SEED = 2 ** 31 + 12345


def small_cell(name: str) -> H.Cell:
    """The cell with every size cut to what a CPU test run holds; the
    comparison and its limits are the cell's own."""
    cell = H.load_cell(name)
    cell.config.update(shape=[20, 20, 200], ranks=[4, 4, 4])
    cell.traffic["check_sample"] = 12
    if cell.traffic["loop"] == "open":
        cell.traffic.update(rate_per_s=24.0, pool_per_extent=1)
        cell.traffic["extents"].update(values=[40, 200], weights=[0.5, 0.5])
    return cell


def run(cell: H.Cell) -> dict:
    return H.run_cell(cell, SEED, 1.0, False, time.perf_counter(), None)


@pytest.mark.parametrize("name", CELLS)
def test_a_sound_run_is_correct(name):
    cell = small_cell(name)
    out = run(cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert set(out["checks"]) == set(cell.checks)
    assert set(out["metrics"]) == set(cell.end_to_end)


@pytest.mark.parametrize("name", CELLS)
def test_the_control_is_not_correct(name):
    cell = small_cell(name)
    repro = H.program()
    loop = H.LOOPS[cell.traffic["loop"]](cell, SEED, repro)
    loop.warm_up()
    win = loop.window(1.0)
    loop.close()
    request = H.request_of(cell)
    answers = H.control_answers(loop.inputs, request, win.sampled, "bf16")
    numbers = H.compare(loop.inputs, request, win.sampled, answers=answers)
    correct, shown = H.judge(numbers, cell.checks, 0)
    assert not correct, shown
    assert any(v["value"] > 3 * v["limit"] for v in shown.values()), shown


@pytest.mark.parametrize("name", CELLS)
def test_an_answer_altered_where_it_is_produced_is_not_correct(
        name, monkeypatch):
    repro = H.program()
    from repro.core.api import TuckerPlan
    from repro.core.sthosvd import TuckerTensor
    execute = TuckerPlan.execute

    def altered(self, x, **kw):
        res = execute(self, x, **kw)
        core = res.tucker.core
        core = core.at[(0,) * core.ndim].multiply(1.01)
        return dataclasses.replace(
            res, tucker=TuckerTensor(core=core, factors=res.tucker.factors))

    monkeypatch.setattr(TuckerPlan, "execute", altered)
    assert repro.core.api.TuckerPlan.execute is altered
    out = run(small_cell(name))
    assert not out["correct"], out["checks"]
    assert out["checks"]["core_resid"]["value"] > \
        out["checks"]["core_resid"]["limit"]


@pytest.mark.parametrize("scale, fails", [
    (0.5, "bound_excess"),    # a bound below the error it certifies
    (2.5, "error_bound"),     # a bound above the error target
])
def test_a_wrong_reported_error_bound_is_not_correct(scale, fails,
                                                     monkeypatch):
    repro = H.program()
    from repro.core.api import TuckerPlan
    execute = TuckerPlan.execute

    def misreported(self, x, **kw):
        res = execute(self, x, **kw)
        if res.error_bound is None:
            return res
        return dataclasses.replace(res, error_bound=res.error_bound * scale)

    monkeypatch.setattr(TuckerPlan, "execute", misreported)
    out = run(small_cell("cavity.target"))
    assert not out["correct"], out["checks"]
    assert out["checks"][fails]["value"] > out["checks"][fails]["limit"]


def test_without_a_tpu_the_command_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(HERE / "run.py"), "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs a TPU" in proc.stderr


def test_the_manifest_names_a_file_for_every_cell_and_metric():
    manifest = json.loads((H.ROOT / "BENCHMARK.json").read_text())
    for w in manifest["workloads"]:
        cell = H.load_cell(w["name"])
        assert cell.end_to_end and cell.per_layer
        assert "setup_s" in cell.end_to_end
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert (HERE / "metrics" / f"{m['name']}.py").is_file()
