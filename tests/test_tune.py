"""Autotune subsystem: measurement store, harvesting, stratified training,
calibration, and the (platform, backend) selector resolution order."""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import cost_model as cm_mod
from repro.core.api import TuckerConfig, plan
from repro.core.cost_model import CostModel
from repro.core import selector as sel_mod
from repro.core.selector import Selector, default_selector
from repro.tune import (
    Measurement,
    RecordStore,
    fit_cost_model,
    labeled_examples,
    recording,
    train_stratified,
)
from repro.tune.records import COLLECT, HARVEST


def M(i, r, j, method, seconds, *, backend="matfree", platform="cpu",
      device="box", source=COLLECT, dtype="float32", order=3):
    return Measurement(platform=platform, backend=backend, device=device,
                       i_n=i, r_n=r, j_n=j, method=method, seconds=seconds,
                       dtype=dtype, order=order, source=source)


@pytest.fixture
def model_env(tmp_path, monkeypatch):
    """Isolated model dir + empty selector cache."""
    monkeypatch.setattr(sel_mod, "_DEFAULT_MODEL_DIR", tmp_path / "models")
    monkeypatch.setattr(sel_mod, "_DEFAULT_BY_PLATFORM", {})
    return tmp_path


def synthetic_records(*, backend="matfree", platform="cpu", als_faster_above=64,
                      n=40, seed=0):
    """Labeled-by-construction records: als wins iff i_n > threshold.
    Seconds are overhead + a flop-proportional term, so calibration fits
    cleanly; the term stays under the 1e-4 s overhead gap at every size
    here, so the overhead alone decides each label."""
    rng = np.random.default_rng(seed)
    out = []
    for i in np.unique(np.geomspace(4, 256, n).astype(int)):
        r = max(1, int(i) // 4)
        j = int(rng.integers(64, 1024))
        slow, fast = 2e-4, 1e-4
        te = slow if i > als_faster_above else fast
        ta = fast if i > als_faster_above else slow
        te += 1e-13 * cm_mod.eig_flops(i, r, j)
        ta += 1e-13 * cm_mod.als_flops(i, r, j)
        out.append(M(int(i), r, j, "eig", te, backend=backend,
                     platform=platform))
        out.append(M(int(i), r, j, "als", ta, backend=backend,
                     platform=platform))
    return out


class TestRecordStore:
    def test_roundtrip(self, tmp_path):
        store = RecordStore(tmp_path / "s.jsonl")
        ms = [M(10, 2, 50, "eig", 0.01), M(10, 2, 50, "als", 0.02)]
        assert store.append(ms) == 2
        got = store.load()
        assert got == ms          # frozen dataclass equality
        assert got[0].key() != got[1].key()
        assert got[0].problem_key() == got[1].problem_key()

    def test_dedup_keeps_fastest(self, tmp_path):
        store = RecordStore(tmp_path / "s.jsonl")
        store.append([M(10, 2, 50, "eig", 0.05),
                      M(10, 2, 50, "eig", 0.01),
                      M(10, 2, 50, "eig", 0.03)])
        best = store.dedup()
        assert len(best) == 1
        assert next(iter(best.values())).seconds == 0.01

    def test_digest_stable_under_order_and_duplicates(self, tmp_path):
        a = RecordStore(tmp_path / "a.jsonl")
        b = RecordStore(tmp_path / "b.jsonl")
        m1, m2 = M(10, 2, 50, "eig", 0.01), M(20, 4, 30, "als", 0.02)
        a.append([m1, m2])
        b.append([m2, m1, m1])    # reordered + an exact duplicate
        assert a.digest() == b.digest()
        b.append([M(9, 2, 9, "eig", 0.5)])
        assert a.digest() != b.digest()

    def test_filter_and_merge(self, tmp_path):
        a = RecordStore(tmp_path / "a.jsonl")
        b = RecordStore(tmp_path / "b.jsonl")
        a.append([M(10, 2, 50, "eig", 0.01, backend="matfree"),
                  M(10, 2, 50, "eig", 0.02, backend="explicit")])
        b.append([M(10, 2, 50, "eig", 0.005, backend="matfree"),   # faster
                  M(99, 9, 99, "als", 0.5, backend="matfree")])    # new
        assert len(a.filter(backend="explicit")) == 1
        assert a.merge_from(b) == 2
        assert a.dedup()[M(10, 2, 50, "eig", 0).key()].seconds == 0.005

    def test_partial_tail_line_skipped(self, tmp_path):
        store = RecordStore(tmp_path / "s.jsonl")
        store.append([M(10, 2, 50, "eig", 0.01)])
        with store.path.open("a") as f:
            f.write('{"platform": "cpu", "i_n": 5')   # interrupted append
        assert len(store.load()) == 1

    def test_compact(self, tmp_path):
        store = RecordStore(tmp_path / "s.jsonl")
        store.append([M(10, 2, 50, "eig", 0.05), M(10, 2, 50, "eig", 0.01)])
        digest = store.digest()
        assert store.compact() == 1
        assert len(store) == 1 and store.digest() == digest


class TestLabeling:
    def test_pairing_requires_both_methods(self):
        ms = [M(10, 2, 50, "eig", 0.02), M(10, 2, 50, "als", 0.01),
              M(77, 7, 70, "eig", 0.5)]         # one-sided → unlabeled
        feats, labels, times = labeled_examples(ms)
        assert len(labels) == 1
        assert labels[0] == 1                   # als was faster
        assert tuple(times[0]) == (0.02, 0.01)
        assert feats[0][0] == 10

    def test_best_of_duplicates_labels(self):
        ms = [M(10, 2, 50, "eig", 0.02), M(10, 2, 50, "eig", 0.005),
              M(10, 2, 50, "als", 0.01)]
        _, labels, times = labeled_examples(ms)
        assert labels[0] == 0                   # best eig (0.005) beats als
        assert tuple(times[0]) == (0.005, 0.01)


class TestTrainingAndResolution:
    def test_stratified_training_and_resolution_order(self, model_env):
        store = RecordStore(model_env / "s.jsonl")
        # two backends with INVERTED crossovers — one pooled tree can't
        # serve both, which is exactly why resolution is backend-first
        store.append(synthetic_records(backend="m1", als_faster_above=64))
        store.append(synthetic_records(backend="m2", als_faster_above=-1,
                                       seed=1))   # m2: als always wins
        written = train_stratified(store, platform="cpu")
        names = {p.split("/")[-1] for p in written}
        assert names == {"selector_cpu_m1.json", "selector_cpu_m2.json",
                         "selector_cpu.json"}
        for info in written.values():
            assert info["store_digest"] == store.digest()
            assert info["n_examples"] >= 12

        sel_mod._DEFAULT_BY_PLATFORM.clear()
        s1 = default_selector("cpu", "m1")
        s2 = default_selector("cpu", "m2")
        assert s1.backend == "m1" and s2.backend == "m2"
        assert s1(i_n=16, r_n=4, j_n=256) == "eig"   # below m1 crossover
        assert s2(i_n=16, r_n=4, j_n=256) == "als"   # m2: als everywhere
        # unknown backend → platform-pooled tree, not the cost model
        pooled = default_selector("cpu", "no_such_backend")
        assert pooled.tree is not None and pooled.backend is None
        # caching is per (platform, backend)
        assert default_selector("cpu", "m1") is s1
        assert s1 is not s2

    def test_resolution_falls_back_to_cost_model(self, model_env):
        sel = default_selector("cpu", "matfree")    # no files at all
        assert sel.tree is None
        assert sel(i_n=30648, r_n=10, j_n=2256) == "als"   # Eq.4/5 fallback

    def test_trained_model_prices_plans(self, model_env):
        """A trained+calibrated model makes plan schedules carry
        predicted_s, and traces expose predicted-vs-actual."""
        store = RecordStore(model_env / "s.jsonl")
        store.append(synthetic_records())
        train_stratified(store, platform="cpu")
        sel_mod._DEFAULT_BY_PLATFORM.clear()
        assert default_selector("cpu", "matfree").cost_model.calibrated
        p = plan((24, 16, 12), jnp.float32, TuckerConfig(ranks=(4, 4, 4)))
        assert all(s.predicted_s > 0 for s in p.schedule)
        res = p.execute(jnp.zeros((24, 16, 12), jnp.float32))
        assert all(t.predicted_s > 0 for t in res.trace)

    def test_selector_save_without_tree_raises(self, tmp_path):
        with pytest.raises(ValueError, match="no trained tree"):
            Selector(platform="cpu").save(tmp_path / "x.json")

    def test_train_and_save_platform_agreement(self, model_env, monkeypatch):
        """The passed platform labels the model, names the file, and keys
        the cache — regardless of the box it trained on."""
        import importlib

        # NB: the attribute ``repro.tune.collect`` is the collect FUNCTION
        # (re-exported in __init__), shadowing the submodule — same pattern
        # as repro.core.plan; resolve the module via import machinery
        collect_mod = importlib.import_module("repro.tune.collect")
        from repro.tune import train as train_mod

        def fake_collect(**kw):
            rng = np.random.default_rng(0)
            feats = np.stack([sel_mod.extract_features(i, r, j)
                              for i, r, j in rng.integers(2, 500, (60, 3))])
            labels = (feats[:, 0] > 100).astype(int)
            return feats, labels, np.zeros((60, 2))

        monkeypatch.setattr(collect_mod, "collect_samples", fake_collect)
        info = train_mod.train_and_save(platform="gpu")
        assert info["n_train"] > 0
        path = sel_mod.model_path("gpu")
        assert path.exists()
        loaded = Selector.load(path)
        assert loaded.platform == "gpu"
        assert sel_mod._DEFAULT_BY_PLATFORM[("gpu", None)].platform == "gpu"

    def test_v1_model_file_still_loads(self, tmp_path):
        from repro.core.dtree import DecisionTree
        t = DecisionTree(max_depth=2).fit(
            np.array([[1.0], [2.0], [3.0], [4.0]] * 5),
            np.array([0, 0, 1, 1] * 5))
        (tmp_path / "old.json").write_text(json.dumps(
            {"platform": "cpu", "tree": t.to_dict(),
             "trained_range": [[1, 1, 1], [9, 9, 9]]}))
        s = Selector.load(tmp_path / "old.json")
        assert s.backend is None and s.tree is not None
        assert s.cost_model.source == "textbook"


class TestHarvest:
    def test_recording_harvests_executed_plans(self, tmp_path, model_env):
        store = RecordStore(tmp_path / "h.jsonl")
        x = jnp.asarray(np.random.default_rng(0).standard_normal((16, 12, 10)),
                        jnp.float32)
        with recording(store) as sink:
            for methods in ("eig", "als"):
                p = plan(x.shape, x.dtype,
                         TuckerConfig(ranks=(4, 4, 4), methods=methods))
                res = p.execute(x)          # recording context forces timing
                assert all(t.seconds > 0 for t in res.trace)
            assert len(sink.measurements) == 6
        got = store.load()
        assert len(got) == 6
        assert all(m.source == HARVEST and m.seconds > 0 for m in got)
        assert all(m.platform == jax.default_backend() for m in got)
        # eig+als ran on identical problems → records pair into labeled
        # training examples: the full online flywheel roundtrip
        feats, labels, _ = labeled_examples(got)
        assert len(labels) == 3

    def test_execute_record_matches_unrecorded(self, model_env):
        x = jnp.asarray(np.random.default_rng(1).standard_normal((12, 10, 8)),
                        jnp.float32)
        cfg = TuckerConfig(ranks=(3, 3, 3), methods="eig")
        p = plan(x.shape, x.dtype, cfg)
        plain = p.execute(x)
        rec = p.execute(x, record=True)
        assert all(t.seconds > 0 for t in rec.trace)
        assert all(t.seconds == 0 for t in plain.trace)
        np.testing.assert_allclose(np.abs(np.asarray(rec.tucker.core)),
                                   np.abs(np.asarray(plain.tucker.core)),
                                   rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("variant", ["thosvd", "hooi"])
    def test_record_covers_all_variants(self, variant, model_env):
        x = jnp.asarray(np.random.default_rng(2).standard_normal((10, 9, 8)),
                        jnp.float32)
        cfg = TuckerConfig(ranks=(3, 3, 3), methods="eig", variant=variant,
                           hooi_iters=1)
        res = plan(x.shape, x.dtype, cfg).execute(x, record=True)
        assert len(res.trace) == len(plan(x.shape, x.dtype, cfg).schedule)
        assert all(t.seconds > 0 for t in res.trace)
        assert float(res.tucker.rel_error(x)) < 1.0


class TestCalibration:
    def test_fit_recovers_scales_and_constants(self):
        """Synthetic seconds generated FROM the model → fit recovers it."""
        rng = np.random.default_rng(3)
        truth = CostModel(c_eig=40.0, c_qr=1.0,
                          eig_scale=2e-10, als_scale=1e-10,
                          eig_overhead_s=3e-4, als_overhead_s=8e-4,
                          source="calibrated")
        ms = []
        for _ in range(40):
            i = int(rng.integers(8, 300))
            r = max(1, i // 4)
            j = int(rng.integers(64, 4096))
            ms.append(M(i, r, j, "eig",
                        truth.predict_seconds("eig", i, r, j)))
            ms.append(M(i, r, j, "als",
                        truth.predict_seconds("als", i, r, j)))
        cm = fit_cost_model(ms)
        assert cm is not None and cm.calibrated
        assert cm.c_eig == pytest.approx(40.0, rel=0.05)
        assert cm.eig_scale == pytest.approx(2e-10, rel=0.05)
        assert cm.als_overhead_s == pytest.approx(8e-4, rel=0.1)

    def test_calibration_flips_predicted_best(self):
        """Measurements where EIG FLOPs are pathologically slow flip the
        analytic choice at a query the textbook model calls for EIG."""
        q = (6, 5, 30648 * 376)                     # textbook: eig wins big
        assert cm_mod.predicted_best(*q) == "eig"
        ms = []
        rng = np.random.default_rng(4)
        for _ in range(20):
            i = int(rng.integers(4, 64))
            r = max(1, i // 4)
            j = int(rng.integers(1024, 1 << 16))
            # eig FLOPs cost 1000× als FLOPs on this "hardware"
            ms.append(M(i, r, j, "eig", 1e-7 * cm_mod.eig_flops(i, r, j)))
            ms.append(M(i, r, j, "als", 1e-10 * cm_mod.als_flops(i, r, j)))
        cm = fit_cost_model(ms)
        assert cm is not None and cm.calibrated
        assert cm.predicted_best(*q) == "als"

    def test_fit_returns_none_when_starved(self):
        assert fit_cost_model([M(8, 2, 64, "eig", 0.1)]) is None

    def test_out_of_range_guardrail_uses_calibrated_model(self):
        """In-range queries hit the tree; out-of-range queries defer to the
        selector's EMBEDDED calibrated cost model, not the textbook one."""
        from repro.core.dtree import DecisionTree
        feats = np.stack([sel_mod.extract_features(i, 4, 64)
                          for i in range(8, 64)])
        tree = DecisionTree(max_depth=1).fit(feats,
                                             np.zeros(len(feats), int))
        calibrated = CostModel(eig_scale=1e-3, als_scale=1e-12,
                               source="calibrated")   # als wins everywhere
        sel = Selector(tree=tree, platform="cpu", backend="matfree",
                       trained_range=((8, 4, 64), (63, 4, 64)),
                       cost_model=calibrated)
        assert sel(i_n=32, r_n=4, j_n=64) == "eig"          # tree, in range
        q = dict(i_n=6, r_n=5, j_n=30648 * 376)             # out of range
        assert Selector(tree=tree, platform="cpu",
                        trained_range=sel.trained_range)(**q) == "eig"
        assert sel(**q) == "als"                            # calibrated

    def test_calibrate_store_writes_per_backend_files(self, model_env):
        store = RecordStore(model_env / "s.jsonl")
        store.append(synthetic_records(backend="matfree"))
        store.append(synthetic_records(backend="explicit", seed=5))
        from repro.tune import calibrate_store
        written = calibrate_store(store, platform="cpu")
        names = {p.split("/")[-1] for p in written}
        assert names == {"cost_cpu_matfree.json", "cost_cpu_explicit.json"}
        sel_mod._DEFAULT_BY_PLATFORM.clear()
        # no tree model on disk → fallback selector picks up the calibration
        sel = default_selector("cpu", "matfree")
        assert sel.tree is None and sel.cost_model.calibrated


class TestCLI:
    def test_collect_train_report_roundtrip(self, tmp_path, model_env,
                                            capsys):
        from repro.tune.cli import main
        store = str(tmp_path / "cli.jsonl")
        assert main(["collect", "--store", store, "--n-tensors", "4",
                     "--min-dim", "6", "--max-dim", "20", "--reps", "1",
                     "--quiet"]) == 0
        assert main(["harvest", "--store", store, "--smoke"]) == 0
        mdir = str(tmp_path / "m")
        assert main(["train", "--store", store, "--platform", "cpu",
                     "--model-dir", mdir, "--min-examples", "6"]) == 0
        sel = Selector.load(next(iter(
            Path(mdir).glob("selector_cpu.json"))))
        assert sel.tree is not None
        assert sel.meta["store_digest"] == RecordStore(store).digest()
        assert main(["report", "--store", store, "--model-dir", mdir]) == 0
        out = capsys.readouterr().out
        assert "selector_cpu.json" in out

    def test_collect_times_each_step_of_a_named_tensor(self, tmp_path,
                                                       monkeypatch):
        from repro.tune.cli import main
        from repro.tune.collect import MAX_EIG_DIM
        monkeypatch.setitem(MAX_EIG_DIM, "cpu", 10)
        store = str(tmp_path / "named.jsonl")
        assert main(["collect", "--store", store, "--n-tensors", "0",
                     "--tensor", "12x8x6:3", "--reps", "1", "--quiet"]) == 0
        steps = {(m.i_n, m.r_n, m.j_n, m.method)
                 for m in RecordStore(store).load()}
        # mode n's input has the modes before it shrunk to their ranks; the
        # 12-long mode is past the platform's MAX_EIG_DIM: ALS alone
        assert steps == {(12, 3, 48, "als"),
                         (8, 3, 18, "eig"), (8, 3, 18, "als"),
                         (6, 3, 9, "eig"), (6, 3, 9, "als")}

    def test_tensor_spec_needs_a_rank_per_mode(self, tmp_path):
        from repro.tune.cli import main
        with pytest.raises(SystemExit, match="SHAPE:RANKS"):
            main(["collect", "--store", str(tmp_path / "x.jsonl"),
                  "--tensor", "12x8x6:3x3"])
