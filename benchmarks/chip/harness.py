"""What the benchmark finds by name, and the pieces every run shares.

``BENCHMARK.json`` at the repository root lists the configurations, cells
and metrics.  Everything else is found from the names in it:

* ``configs/<config>.json``: the configuration as it is run (the file that
  ``BENCHMARK.json`` names for it);
* ``traffic/<traffic>.json``: a traffic mix's parameters; its ``driver``
  names the general module in ``drivers/`` that runs it;
* ``limits/<workload>.json``: the numbers compared with the plain reference
  in that cell, each with its limit (a driver may read more numbers than a
  cell compares);
* ``metrics/<metric>.py``: one reader per per-layer metric, a function
  ``read(ctx)`` that returns the metric's value or ``None``.

Adding a cell, a configuration, a traffic mix or a per-layer metric is
adding such files and entries; no file here changes.  The data files are
read under a checkout's root (``root``, the repository by default); the
drivers and readers are code and come from beside this file.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
from typing import Callable, Dict, List, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
REL = os.path.relpath(HERE, ROOT)               # benchmarks/chip
CACHE_DIR = os.path.join(HERE, ".cache")        # git-ignored
TRACE_DIR = os.path.join(HERE, ".traces")       # git-ignored


class BenchError(Exception):
    """A run that cannot produce a result."""


# ------------------------------------------------------------ discovery
def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_spec(root: str = ROOT) -> dict:
    return _json(os.path.join(root, "BENCHMARK.json"))


def _named(entries: List[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise BenchError(f"no {what} named {name!r} in BENCHMARK.json")


def workload(spec: dict, name: str) -> dict:
    return _named(spec["workloads"], name, "workload")


def config(spec: dict, name: str, root: str = ROOT) -> dict:
    return _json(os.path.join(root, _named(spec["configs"], name,
                                           "configuration")["file"]))


def traffic(name: str, root: str = ROOT) -> dict:
    return _json(os.path.join(root, REL, "traffic", f"{name}.json"))


def limits(workload_name: str, root: str = ROOT) -> dict:
    return _json(os.path.join(root, REL, "limits", f"{workload_name}.json"))


def _module(path: str, label: str):
    if not os.path.exists(path):
        raise BenchError(f"no {label} at {path}")
    spec = importlib.util.spec_from_file_location(
        f"_bench_{label}_{os.path.basename(path)[:-3]}".replace(".", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def driver(name: str, here: str = HERE):
    """The general driver a traffic mix names (``drivers/<name>.py``)."""
    return _module(os.path.join(here, "drivers", f"{name}.py"), "driver")


def metric_reader(name: str, here: str = HERE) -> Callable[[dict],
                                                           Optional[float]]:
    return _module(os.path.join(here, "metrics", f"{name}.py"),
                   "metric").read


def cell_metrics(spec: dict, workload_name: str, group: str) -> List[dict]:
    """The ``end_to_end`` or ``per_layer`` entries a cell reports.  An entry
    with ``workloads`` applies to those cells; an end-to-end entry without
    it applies everywhere.  Every per-layer entry names its cells."""
    if group == "end_to_end":
        return [m for m in spec["end_to_end"]
                if workload_name in m.get("workloads", [workload_name])]
    for m in spec["per_layer"]:
        if "workloads" not in m:
            raise BenchError(f"per-layer metric {m['name']!r} names no "
                             f"workloads")
    return [m for m in spec["per_layer"] if workload_name in m["workloads"]]


# ------------------------------------------------------------ devices
def require_devices(chips: int):
    """The devices a cell runs on.  Raises unless JAX sees at least
    ``chips`` TPU chips: a CPU number is never reported as a device one."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX found {devs[0].platform} "
                         f"({devs[0].device_kind})")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")
    return devs[:chips]


def memory_peak_bytes(devs, program_bytes: int = 0) -> int:
    """Peak bytes on the fullest chip: the larger of the allocator's peak
    (which leaves out a program's temporaries) and ``program_bytes``, the
    footprint the compiler reports for the timed program (arguments,
    outputs and temporaries)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return int(max(max(peaks), program_bytes))


def compiled_bytes(compiled) -> int:
    """Arguments, outputs and temporaries of a compiled program, counting
    donated buffers once."""
    m = compiled.memory_analysis()
    return int(m.argument_size_in_bytes + m.output_size_in_bytes
               - m.alias_size_in_bytes + m.temp_size_in_bytes)


# ------------------------------------------------------------ seeds
def seed_key(seed: int):
    """A JAX PRNG key from a seed of any size: ``PRNGKey`` keeps only the
    low 32 bits of a larger one, so the key is made from the seed's
    ``SeedSequence`` state instead."""
    import jax.numpy as jnp
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jnp.asarray(words, jnp.uint32)


def add_program_path() -> None:
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise BenchError(f"the program is not beside the benchmark "
                         f"(no {src}/repro)")
    if src not in sys.path:
        sys.path.insert(0, src)


def configure_caches() -> Dict[str, str]:
    """Fixed cache directories inside the checkout, set before JAX or the
    program is imported: the persistent compilation cache (which the
    program's ``launch.runtime.enable_compile_cache`` then takes from
    ``JAX_COMPILATION_CACHE_DIR``), the autotune verdicts
    (``REPRO_EXEC_CACHE``) and, unless the machine sets them elsewhere,
    the TPU runtime's logs."""
    dirs = {"JAX_COMPILATION_CACHE_DIR": os.path.join(CACHE_DIR, "jax"),
            "REPRO_EXEC_CACHE": os.path.join(CACHE_DIR, "exec")}
    for var, path in dirs.items():
        os.makedirs(path, exist_ok=True)
        os.environ[var] = path
    # the TPU runtime's logs, which otherwise go to a fixed path under /tmp
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(CACHE_DIR, "tpu_logs"))
    return dirs


@dataclasses.dataclass(frozen=True)
class Cell:
    spec: dict          # BENCHMARK.json
    entry: dict         # the cell's ``workloads`` entry
    config: dict
    traffic: dict
    limits: dict
    driver: object      # the module ``drivers/<traffic driver>.py``


def open_cell(name: str, root: str = ROOT) -> Cell:
    """Everything one cell is run from.  Points the caches into the
    checkout and puts the program on the path before anything imports JAX
    (the drivers do)."""
    spec = load_spec(root)
    entry = workload(spec, name)
    cfg = config(spec, entry["config"], root)
    mix = traffic(entry["traffic"], root)
    lim = limits(name, root)
    add_program_path()
    configure_caches()
    return Cell(spec, entry, cfg, mix, lim, driver(mix["driver"]))


def start_compile_cache() -> None:
    """The program's persistent compilation cache, keeping every program:
    JAX's default keeps only those that took a second or more to compile,
    and the serving cell's hundred small layer programs would otherwise
    compile again in every run."""
    import jax
    from repro.launch.runtime import enable_compile_cache
    # set here too: JAX reads JAX_COMPILATION_CACHE_DIR only when imported
    jax.config.update("jax_compilation_cache_dir", enable_compile_cache())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
