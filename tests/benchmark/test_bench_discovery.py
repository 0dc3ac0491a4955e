"""The harness finds configurations, traffic, limits and metric readers by
the names in ``BENCHMARK.json``, and refuses to run without its chips."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import BENCH, REPO

import harness
import peaks

CELL_E2E = {"gcn-cora.train": {"setup_s", "step_ms"},
            "gcn-arxiv.train": {"setup_s", "step_ms", "train_edges_per_s"},
            "gcn-cora.serve": {"setup_s", "serve_p50_ms", "serve_p95_ms"}}


@pytest.fixture(scope="module")
def spec():
    return harness.load_spec()


def test_every_cell_finds_its_files(spec):
    for cell in spec["workloads"]:
        cfg = harness.config(spec, cell["config"])
        assert cfg["name"] == cell["config"]
        traffic = harness.traffic(cell["traffic"])
        drv = harness.driver(traffic["driver"])
        assert drv.KIND in ("train", "serve")
        limits = harness.limits(cell["name"])
        assert set(limits) and all(v >= 0 for v in limits.values())


def test_every_per_layer_metric_has_a_reader(spec):
    ctx = {"kind": "other", "setup": {}, "window": {}, "trace": None,
           "peaks": None, "flops_per_step": None}
    for m in spec["per_layer"]:
        read = harness.metric_reader(m["name"])
        assert read(ctx) is None        # nothing to read: left out


def test_cells_report_the_metrics_named_for_them(spec):
    for cell, e2e in CELL_E2E.items():
        got = {m["name"] for m in harness.cell_metrics(spec, cell,
                                                       "end_to_end")}
        assert got == e2e
        per_layer = {m["name"] for m in harness.cell_metrics(
            spec, cell, "per_layer")}
        assert "setup.reorder_s" in per_layer
        assert ("setup.schedule_s" in per_layer) == (cell == "gcn-cora.train")
        assert ("serve.hit_rate" in per_layer) == cell.endswith("serve")
        assert all(m["moves"] in e2e for m in harness.cell_metrics(
            spec, cell, "per_layer"))


def test_a_per_layer_metric_without_workloads_is_refused(spec):
    spec = json.loads(json.dumps(spec))
    spec["per_layer"].append({"name": "x", "moves": "step_ms"})
    assert {m["name"] for m in harness.cell_metrics(
        spec, "gcn-arxiv.train", "end_to_end")} == CELL_E2E["gcn-arxiv.train"]
    with pytest.raises(harness.BenchError, match="names no workloads"):
        harness.cell_metrics(spec, "gcn-arxiv.train", "per_layer")


def test_memory_peak_counts_the_compiled_footprint():
    class Chip:
        def __init__(self, peak):
            self.peak = peak

        def memory_stats(self):
            return {"peak_bytes_in_use": self.peak}
    chips = [Chip(10), Chip(30)]
    assert harness.memory_peak_bytes(chips) == 30
    assert harness.memory_peak_bytes(chips, program_bytes=500) == 500
    import jax
    import jax.numpy as jnp
    compiled = jax.jit(lambda a: a @ a).lower(
        jnp.zeros((64, 64), jnp.float32)).compile()
    assert harness.compiled_bytes(compiled) >= 2 * 64 * 64 * 4


def test_a_new_configuration_is_found_by_name(checkout):
    spec = harness.load_spec(str(checkout))
    cfg = harness.config(spec, "gcn-tiny", str(checkout))
    assert cfg["graph"]["num_nodes"] == 300
    assert harness.workload(spec, "gcn-tiny.train")["config"] == "gcn-tiny"
    assert harness.limits("gcn-tiny.train", str(checkout)) == \
        harness.limits("gcn-cora.train")
    with pytest.raises(harness.BenchError):
        harness.config(spec, "gcn-missing", str(checkout))


def test_an_unknown_device_kind_raises():
    assert peaks.lookup("TPU v5 lite")["flops_bf16"] == 197e12
    with pytest.raises(KeyError, match="no peak numbers"):
        peaks.lookup("TPU v99")


def test_the_look_for_chips_refuses_a_cpu():
    with pytest.raises(harness.BenchError, match="no TPU"):
        harness.require_devices(1)


def test_seeds_beyond_32_bits_give_distinct_keys():
    import numpy as np
    a = np.asarray(harness.seed_key(5))
    b = np.asarray(harness.seed_key(5 + 2 ** 32))
    assert a.dtype == np.uint32 and a.shape == (2,)
    assert not np.array_equal(a, b)


def _command(root, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", HOME=str(tmp_path))
    return subprocess.run(
        [sys.executable, os.path.join(root, "benchmarks", "chip", "run.py"),
         "--workload", "gcn-cora.train", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("with_program", [True, False])
def test_the_command_prints_no_result_without_chip_or_program(
        tmp_path, with_program):
    """In a copy of the checkout: without a TPU, and in a directory that
    holds only BENCHMARK.json and the benchmark's paths."""
    root = tmp_path / "checkout"
    spec = harness.load_spec()
    for path in spec["paths"]:
        shutil.copytree(os.path.join(REPO, path), root / path,
                        ignore=shutil.ignore_patterns(".cache", ".traces",
                                                      "__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    if with_program:
        os.symlink(os.path.join(REPO, "src"), root / "src")
    out = _command(str(root), tmp_path)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert ("no TPU" if with_program else "program") in out.stderr
    assert os.path.isdir(BENCH)
