"""Fixtures for the tests of the chip benchmark (``benchmarks/chip``).

The benchmark's modules are imported from its own directory.  ``checkout``
builds a small checkout in a temporary directory: the repository's
``BENCHMARK.json`` plus two configurations that exist nowhere else, found
by name like any other, at sizes a CPU test can run.
"""
import json
import os
import shutil
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmarks", "chip")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

# small stand-ins for the cells, at the configurations' widths on smaller
# graphs, keyed by the cell whose traffic and limits they borrow
TINY = {
    "gcn-tiny.train": ("gcn-cora.train", "gcn-tiny",
                       {"num_nodes": 300, "num_edges": 1500}),
    "gcn-tiny3.train": ("gcn-arxiv.train", "gcn-tiny3",
                        {"num_nodes": 2000, "num_edges": 20000}),
    "gcn-tiny.serve": ("gcn-cora.serve", "gcn-tiny", None),
}


@pytest.fixture
def checkout(tmp_path, monkeypatch):
    """Root of a checkout with the tiny cells added, its caches under
    ``tmp_path``."""
    import harness

    spec = harness.load_spec(REPO)
    base = tmp_path / harness.REL
    for sub in ("configs", "traffic", "limits"):
        (base / sub).mkdir(parents=True)
    shutil.rmtree(base / "traffic")
    shutil.copytree(os.path.join(BENCH, "traffic"), base / "traffic")
    for name, (like, cfg_name, graph) in TINY.items():
        cell = harness.workload(spec, like)
        if graph is not None:
            cfg = harness.config(spec, cell["config"], REPO)
            cfg["name"] = cfg_name
            cfg["graph"].update(graph)
            rel = f"{harness.REL}/configs/{cfg_name}.json"
            (tmp_path / rel).write_text(json.dumps(cfg))
            spec["configs"].append({"name": cfg_name, "source": "test",
                                    "file": rel, "reduced": [],
                                    "why": "test"})
        spec["workloads"].append(dict(cell, name=name, config=cfg_name))
        for m in spec["end_to_end"] + spec["per_layer"]:
            if like in m.get("workloads", []):
                m["workloads"].append(name)
        shutil.copy(os.path.join(BENCH, "limits", f"{like}.json"),
                    base / "limits" / f"{name}.json")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    monkeypatch.setattr(harness, "CACHE_DIR", str(tmp_path / "cache"))
    # no persistent compilation cache in a test process
    monkeypatch.setattr(harness, "start_compile_cache", lambda: None)
    for var in ("JAX_COMPILATION_CACHE_DIR", "REPRO_EXEC_CACHE",
                "TPU_LOG_DIR"):
        monkeypatch.setenv(var, str(tmp_path / "unused"))
    return tmp_path


def run_cell(root, workload, seed=3, seconds=1.0, trace=0):
    """One run of ``run.py`` in this process on the CPU devices."""
    import jax
    import run

    args = run.parse(["--workload", workload, "--seed", str(seed),
                      "--seconds", str(seconds), "--trace", str(trace)])
    return run.run(args, devices=lambda chips: jax.devices()[:chips],
                   root=str(root))
