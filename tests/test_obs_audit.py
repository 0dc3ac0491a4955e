"""Cost-model audit (PR 7 tentpole): calibration-table computation and
device_sig-keyed round-trip, misrank drift detection, evidence joins from
the autotune cache / traces / BENCH documents, and the summary one-pager."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.obs import audit
from repro.obs.audit import (Observation, class_key, cand_class, spearman,
                             compute_calibration, find_misranks,
                             calibration_path, save_calibration,
                             load_calibration, class_ratios,
                             observations_from_cache, observations_from_trace,
                             trace_device_sig, forward_verdict_findings,
                             bench_findings, render_report)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ============================================================ class keys
def test_class_key_and_cand_class():
    assert class_key("coo", 128, True, "update_first") == \
        "coo|bm128|c1|update_first"
    assert class_key("jnp", 64, False) == "jnp|bm64|c0|-"
    # 5-tuple layer candidate: fuse is folded out (the model owns the credit)
    a = cand_class(("aggregate_first", False, "coo", 128, True))
    b = cand_class(("aggregate_first", True, "coo", 128, True))
    assert a == b == "coo|bm128|c1|aggregate_first"
    # 3-tuple graph candidate
    assert cand_class(("pallas", 128, False)) == "pallas|bm128|c0|-"


# ============================================================== spearman
def test_spearman_basic():
    assert spearman([1, 2, 3, 4], [10, 20, 30, 40]) == pytest.approx(1.0)
    assert spearman([1, 2, 3, 4], [40, 30, 20, 10]) == pytest.approx(-1.0)
    # monotone nonlinear is still rank-perfect
    x = [1.0, 2.0, 3.0, 4.0, 5.0]
    assert spearman(x, [v ** 3 for v in x]) == pytest.approx(1.0)
    # constant side -> 0 (no ranking information)
    assert spearman([1, 2, 3], [7, 7, 7]) == 0.0
    # ties are averaged, not order-dependent
    assert spearman([1, 1, 2], [5, 5, 9]) == pytest.approx(1.0)


# ===================================================== calibration table
def _obs(group, ckey, us, model, label="cand"):
    return Observation(group=group, ckey=ckey, label=label, us=us,
                       model=model, source="test")


def test_compute_calibration_ratios_and_groups():
    # class A: consistent 2x us/model; class B: consistent 10x
    rows = [_obs("g1", "A", 200.0, 100.0), _obs("g1", "A", 20.0, 10.0),
            _obs("g1", "B", 3000.0, 300.0), _obs("g2", "B", 100.0, 10.0)]
    t = compute_calibration(rows, "cpu")
    assert t["schema"] == "repro.obs/calibration@1"
    assert t["device_sig"] == "cpu" and t["n_obs"] == 4
    assert t["classes"]["A"]["ratio"] == pytest.approx(2.0)
    assert t["classes"]["B"]["ratio"] == pytest.approx(10.0)
    # perfect fit -> zero relative error
    assert t["classes"]["A"]["rel_err_p90"] == pytest.approx(0.0)
    # g1 has 3 candidates, model and measured rank identically
    assert t["groups"]["g1"]["spearman"] == pytest.approx(1.0)
    assert t["groups"]["g1"]["n_cands"] == 3
    # single-candidate groups carry no rank information
    assert "g2" not in t["groups"]
    assert t["global_ratio"] == pytest.approx(
        float(np.median([2.0, 2.0, 10.0, 10.0])))


def test_compute_calibration_empty():
    t = compute_calibration([], "cpu")
    assert t["n_obs"] == 0 and t["global_ratio"] == 1.0
    assert t["classes"] == {} and t["misranks"] == []


def test_find_misranks():
    # model prefers A (50 < 100) but A measured 3x slower than B
    rows = [_obs("g", "A", 300.0, 50.0, label="A"),
            _obs("g", "B", 100.0, 100.0, label="B")]
    mis = find_misranks(rows, tol=1.25)
    assert len(mis) == 1
    m = mis[0]
    assert m["model_prefers"] == "A" and m["measured_prefers"] == "B"
    assert m["measured_slowdown"] == pytest.approx(3.0)
    # agreement (or within tolerance) -> no finding
    ok = [_obs("g", "A", 90.0, 50.0, label="A"),
          _obs("g", "B", 100.0, 100.0, label="B")]
    assert find_misranks(ok, tol=1.25) == []
    # cross-group pairs are never compared
    x = [_obs("g1", "A", 300.0, 50.0, label="A"),
         _obs("g2", "B", 100.0, 100.0, label="B")]
    assert find_misranks(x, tol=1.25) == []


# ======================================= persistence + device_sig keying
def test_calibration_round_trip_keyed_by_device_sig(tmp_path):
    cache = str(tmp_path)
    t_cpu = compute_calibration(
        [_obs("g", "A", 200.0, 100.0)], "cpu")
    t_tpu = compute_calibration(
        [_obs("g", "A", 5.0, 100.0)], "tpu-TPU-v5e")
    p1 = save_calibration(t_cpu, cache)
    p2 = save_calibration(t_tpu, cache)
    assert p1 == p2 == calibration_path(cache)
    got_cpu = load_calibration("cpu", cache)
    got_tpu = load_calibration("tpu-TPU-v5e", cache)
    assert got_cpu["classes"]["A"]["ratio"] == pytest.approx(2.0)
    assert got_tpu["classes"]["A"]["ratio"] == pytest.approx(0.05)
    assert load_calibration("gpu", cache) is None
    # re-saving a device replaces only that device's table
    t_cpu2 = compute_calibration([_obs("g", "A", 400.0, 100.0)], "cpu")
    save_calibration(t_cpu2, cache)
    assert load_calibration("cpu", cache)["classes"]["A"]["ratio"] == \
        pytest.approx(4.0)
    assert load_calibration("tpu-TPU-v5e", cache)["classes"]["A"][
        "ratio"] == pytest.approx(0.05)


def test_calibration_path_env(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_EXEC_CACHE", str(tmp_path))
    assert calibration_path() == os.path.join(str(tmp_path),
                                              "calibration.json")
    assert calibration_path("/elsewhere") == "/elsewhere/calibration.json"


def test_class_ratios_accepts_table_or_bare_map():
    table = {"classes": {"A": {"ratio": 2.0, "n": 3}, "B": {"n": 1}}}
    assert class_ratios(table) == {"A": 2.0}        # B has no ratio
    assert class_ratios({"A": 3.0}) == {"A": 3.0}   # bare map (tests)
    assert class_ratios(None) == {}


# ================================================== evidence: disk cache
def test_observations_from_cache(tmp_path):
    import importlib
    at = importlib.import_module("repro.exec.autotune")
    sig = "testsig"
    fp = "deadbeefdeadbeef"
    entries = {
        # new-format layer entry: geometry + device_sig present
        f"{fp}:layer:32x8:gcn:r1b1:{sig}:aa": {
            "order": "update_first", "fuse": False, "backend": "coo",
            "bm": 128, "compact": True, "us": 50.0, "model_order":
            "update_first", "n": 100, "e": 500, "d_in": 32, "d_out": 8,
            "mode": "gcn", "device_sig": sig,
            "table": [["aggregate_first", False, "coo", 128, True, 80.0],
                      ["update_first", False, "coo", 128, True, 50.0]]},
        # new-format graph (aggregation-only) entry
        f"{fp}:16:gcn:{sig}:bb": {
            "backend": "coo", "bm": 128, "compact": True, "us": 10.0,
            "n": 100, "e": 500, "d": 16, "mode": "gcn", "device_sig": sig,
            "table": [["coo", 128, True, 10.0], ["jnp", 64, True, 30.0]]},
        # pre-audit-era entry (no geometry): skipped, not an error
        f"{fp}:layer:8x8:gcn:r1b1:{sig}:cc": {
            "order": "update_first", "fuse": False, "backend": "coo",
            "bm": 128, "compact": True, "us": 5.0, "device_sig": sig,
            "table": [["update_first", False, "coo", 128, True, 5.0]]},
        # another device's entry: filtered out
        f"{fp}:layer:32x8:gcn:r1b1:other:dd": {
            "order": "update_first", "fuse": False, "backend": "coo",
            "bm": 128, "compact": True, "us": 1.0, "n": 100, "e": 500,
            "d_in": 32, "d_out": 8, "mode": "gcn", "device_sig": "other",
            "table": [["update_first", False, "coo", 128, True, 1.0]]},
    }
    path = os.path.join(str(tmp_path), "autotune.json")
    with open(path, "w") as f:
        json.dump(entries, f)
    obs_list = observations_from_cache(str(tmp_path), sig=sig)
    assert len(obs_list) == 4          # 2 layer rows + 2 graph rows
    assert all(o.source == "cache" for o in obs_list)
    # every observation was re-modeled with positive cost
    assert all(o.model > 0 and o.us > 0 for o in obs_list)
    layer_keys = {o.ckey for o in obs_list if "first" in o.ckey}
    assert layer_keys == {"coo|bm128|c1|aggregate_first",
                          "coo|bm128|c1|update_first"}
    graph_keys = {o.ckey for o in obs_list if o.ckey.endswith("|-")}
    assert graph_keys == {"coo|bm128|c1|-", "jnp|bm64|c1|-"}
    # layer rows re-model through the real dims-based cost
    lay = {o.ckey: o for o in obs_list if "first" in o.ckey}
    assert lay["coo|bm128|c1|aggregate_first"].model == pytest.approx(
        at.model_layer_cost_dims(
            100, 500, 32, 8, ("aggregate_first", False, "coo", 128, True)))


# ======================================================= evidence: trace
def _trial_span(us, model, order="update_first", failed=False, **extra):
    args = {"backend": "coo", "bm": 128, "compact": True, "order": order,
            "fuse": False, "us": us, "model_cost": model, "n": 100,
            "e": 500, "d_in": 32, "d_out": 8, "mode": "gcn"}
    if failed:
        args["failed"] = True
    args.update(extra)
    return {"ph": "X", "name": "exec.autotune.trial", "ts": 0, "dur": us,
            "pid": 1, "tid": 1, "args": args}


def test_observations_from_trace():
    doc = {"traceEvents": [
        _trial_span(50.0, 100.0, order="update_first"),
        _trial_span(80.0, 120.0, order="aggregate_first"),
        _trial_span(999.0, 100.0, failed=True),          # skipped
        {"ph": "i", "name": "exec.forward.verdict", "ts": 0, "pid": 1,
         "tid": 1, "args": {"source": "dp", "us": 5.0}},  # not a trial
    ], "otherData": {"jax_backend": "cpu", "device_kind": "cpu"}}
    obs_list = observations_from_trace(doc)
    assert len(obs_list) == 2
    assert {o.ckey for o in obs_list} == {"coo|bm128|c1|update_first",
                                          "coo|bm128|c1|aggregate_first"}
    assert all(o.source == "trace" for o in obs_list)
    # both trials share one (graph x shape x mode) rank group
    assert len({o.group for o in obs_list}) == 1
    assert trace_device_sig(doc) == "cpu"
    assert trace_device_sig({"otherData": {
        "jax_backend": "tpu", "device_kind": "TPU v5e"}}) == "tpu-TPU-v5e"
    assert trace_device_sig({}) is None


def test_forward_verdict_findings():
    doc = [{"ph": "i", "name": "exec.forward.verdict", "ts": 0, "pid": 1,
            "tid": 1, "args": {"source": "greedy", "us": 10.0,
                               "table": {"dp": 30.0, "greedy": 10.0}}},
           {"ph": "i", "name": "exec.forward.verdict", "ts": 0, "pid": 1,
            "tid": 1, "args": {"source": "dp", "us": 9.0,
                               "table": {"dp": 9.0, "greedy": 10.0}}}]
    found = forward_verdict_findings(doc, tol=1.25)
    assert len(found) == 1
    assert found[0]["kind"] == "forward_dp_lost_race"
    assert found[0]["slowdown"] == pytest.approx(3.0)


# ================================================ evidence: BENCH documents
def test_bench_findings_surface_cora_anomaly():
    """The committed PR 3 BENCH document records the compacted grid running
    ~0.44x the speed of the padded grid on cora — the audit's drift report
    must surface it with no special-casing."""
    path = os.path.join(REPO, "BENCH_exec_pr3.json")
    if not os.path.exists(path):
        pytest.skip("BENCH_exec_pr3.json not committed")
    with open(path) as f:
        doc = json.load(f)
    findings = bench_findings(doc, tol=1.25)
    slower = [f for f in findings if f["kind"] == "compacted_grid_slower"
              and "cora" in f["name"]]
    assert slower, "the cora compacted-grid anomaly must surface"
    assert slower[0]["speedup_vs_padded"] < 0.6


def test_bench_pr9_bucketed_class_clears_cora_misrank():
    """ISSUE 9's verdict: the degree-binned multi-grid rows in the PR 9
    BENCH must NOT reproduce the compacted-grid misrank — on cora the
    bucketed compacted path measures >=0.9x of padded (the monolithic
    compacted rows are allowed to keep their anomaly; that class is what
    bucketing replaces, not what it repairs)."""
    path = os.path.join(REPO, "BENCH_exec_pr9.json")
    if not os.path.exists(path):
        pytest.skip("BENCH_exec_pr9.json not committed")
    with open(path) as f:
        doc = json.load(f)
    rows = [r for r in doc.get("results", [])
            if "blockell_bucketed_fwd_bwd" in r.get("name", "")]
    assert rows, "PR 9 BENCH must carry bucketed rows"
    cora = [r for r in rows if "cora" in r["name"]]
    assert cora and cora[0]["speedup_vs_padded"] >= 0.9
    assert all(r["speedup_vs_compacted"] > 1.0 for r in rows)
    # every bucketed row carries its occupancy; no drift finding names one
    assert all(r.get("bucket_occupancy") for r in rows)
    bucketed_findings = [
        f for f in bench_findings(doc, tol=1.25)
        if f["kind"] == "compacted_grid_slower" and "bucketed" in f["name"]]
    assert bucketed_findings == []


def test_bench_findings_synthetic():
    doc = {"results": [
        {"name": "a", "speedup_vs_padded": 0.5},
        {"name": "b", "speedup_vs_padded": 1.2},          # fine
        {"name": "c", "order_agrees_with_model": False, "order": "x",
         "model_order": "y"},
        {"name": "d", "order_agrees_with_model": True},
        {"name": "e", "speedup_vs_segment": 0.4},
    ]}
    kinds = sorted(f["kind"] for f in bench_findings(doc))
    assert kinds == ["compacted_grid_slower", "order_model_overruled",
                     "tuned_slower_than_baseline"]


# ================================================================= report
def test_render_report_and_cli(tmp_path):
    doc = {"traceEvents": [_trial_span(50.0, 100.0),
                           _trial_span(80.0, 120.0,
                                       order="aggregate_first")],
           "otherData": {"jax_backend": "cpu", "device_kind": "cpu"}}
    trace_path = os.path.join(str(tmp_path), "t.json")
    with open(trace_path, "w") as f:
        json.dump(doc, f)
    cache = os.path.join(str(tmp_path), "cache")
    rc = audit.main([trace_path, "--cache-dir", cache])
    assert rc == 0
    # the CLI persisted this device's table, keyed by the trace's sig
    table = load_calibration("cpu", cache)
    assert table is not None and table["n_obs"] == 2
    out = render_report(table, [])
    assert "coo|bm128|c1|update_first" in out
    assert "no drift" in out
    # --no-write leaves the store untouched
    os.remove(calibration_path(cache))
    assert audit.main([trace_path, "--cache-dir", cache,
                       "--no-write"]) == 0
    assert not os.path.exists(calibration_path(cache))


def test_roofline_peaks_keyed_by_device_kind(monkeypatch):
    from repro.roofline import hw
    v5e = hw.peaks("TPU v5 lite")
    assert v5e.hbm_bw == 819e9 and v5e.flops_bf16 == 197e12
    # 1e6 / 819e9 us per byte-equivalent is exactly the HBM roofline
    assert hw.hbm_fraction(1e6 / 819e9, "TPU v5 lite") == pytest.approx(1.0)
    for kind in ("cpu", "TPU v4"):
        with pytest.raises(KeyError, match="no peak numbers"):
            hw.hbm_fraction(1.0, kind)
    # on a host without listed peaks the report omits the roofline line
    monkeypatch.setattr(hw, "peaks", lambda kind=None: hw.PEAKS["TPU v5 lite"])
    table = {"device_sig": "tpu", "n_obs": 1, "global_ratio": 1e6 / 819e9,
             "classes": {}, "groups": {}}
    assert "819 GB/s HBM roofline (100.0%)" in render_report(table, [])
    monkeypatch.undo()
    assert "HBM roofline" not in render_report(table, [])


# ================================================================ summary
def test_summary_renders_metrics_and_trace(tmp_path):
    from repro.obs import summary
    metrics = [
        {"schema": "repro.obs/provenance@1", "ts": "2026-01-01T00:00:00",
         "git_sha": "abc", "jax_backend": "cpu", "device_kind": "cpu",
         "jax_version": "0"},
        {"schema": "repro.obs/metric@1", "type": "counter",
         "name": "serve.flush", "labels": {"reason": "full"}, "value": 7},
        {"schema": "repro.obs/metric@1", "type": "gauge",
         "name": "serve.cache.hit_rate", "labels": {"layer": "0"},
         "value": 0.93},
        {"schema": "repro.obs/metric@1", "type": "gauge",
         "name": "serve.queue_depth_hwm", "labels": {}, "value": 6},
        {"schema": "repro.obs/metric@1", "type": "histogram",
         "name": "serve.latency_s", "labels": {}, "count": 10, "sum": 1.0,
         "mean": 0.1, "min": 0.05, "max": 0.3, "p50": 0.09, "p90": 0.2,
         "p99": 0.3},
        {"schema": "repro.obs/event@1", "name": "bench.row", "us": 5.0},
    ]
    jsonl = os.path.join(str(tmp_path), "m.jsonl")
    with open(jsonl, "w") as f:
        for r in metrics:
            f.write(json.dumps(r) + "\n")
    trace = os.path.join(str(tmp_path), "t.json")
    with open(trace, "w") as f:
        json.dump({"traceEvents": [
            {"ph": "X", "name": "exec.plan.compile", "ts": 0, "dur": 2000,
             "pid": 1, "tid": 1},
            {"ph": "X", "name": "exec.plan.compile", "ts": 0, "dur": 1000,
             "pid": 1, "tid": 1},
            {"ph": "i", "name": "v", "ts": 0, "pid": 1, "tid": 1}]}, f)

    kind, recs = summary.load_file(jsonl)
    assert kind == "metrics"
    out = summary.render_metrics(recs)
    assert "serve.flush{reason=full}" in out and "7" in out
    assert "serve.cache.hit_rate{layer=0}" in out
    assert "serve.queue_depth_hwm" in out
    assert "p99" in out and "bench.row" in out

    kind, doc = summary.load_file(trace)
    assert kind == "trace"
    out = summary.render_trace(doc, top=5)
    assert "exec.plan.compile" in out
    stats = summary.span_stats(doc)
    assert stats[0]["count"] == 2
    assert stats[0]["total_ms"] == pytest.approx(3.0)

    assert summary.main([jsonl, trace, "--top", "3"]) == 0
    assert summary.main([os.path.join(str(tmp_path), "missing.json")]) == 1


def test_summary_cli_module_runs(tmp_path):
    jsonl = os.path.join(str(tmp_path), "m.jsonl")
    with open(jsonl, "w") as f:
        f.write(json.dumps({"schema": "repro.obs/provenance@1",
                            "ts": "t", "git_sha": "s", "jax_backend": "cpu",
                            "device_kind": "cpu"}) + "\n")
    env = dict(os.environ,
               PYTHONPATH=os.path.join(REPO, "src"), JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-m", "repro.obs.summary", jsonl],
                      capture_output=True, text=True, env=env, timeout=120)
    assert r.returncode == 0, r.stderr
    assert "sha=s" in r.stdout
