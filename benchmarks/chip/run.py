"""Run one cell of the benchmark on the chips of this machine.

    python3 benchmarks/chip/run.py --workload gcn-cora.train --seed 7 \
        --seconds 10 --trace 0

One process: it checks that JAX sees the TPU chips the cell asks for, sets
up the cell (graph, reorder, schedule, compile, warm-up: ``setup_s``),
measures for ``--seconds``, and compares what the timed path produced with
the plain reference once the window has closed and the program's state is
freed.  With ``--trace 1`` it then traces a further short window and
reports the cell's per-layer metrics instead of its end-to-end ones.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (``breakdown`` too when
traced), and last ``checks``, each number compared with the reference
beside its limit; the same checks are the last lines of standard error.
Without a TPU, or without the program beside the benchmark, it exits
non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402


class Timers(dict):
    """Host-clock seconds of named set-up phases."""

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0 = time.perf_counter()
        yield
        self[name] = self.get(name, 0.0) + time.perf_counter() - t0


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def run(args, devices=harness.require_devices, root=harness.ROOT) -> dict:
    """One run of one cell; returns the result line's object.  ``devices``
    and ``root`` are for tests: the look for chips, and the checkout whose
    ``BENCHMARK.json`` and data files are read."""
    c = harness.open_cell(args.workload, root)
    spec, cell, drv = c.spec, c.entry, c.driver
    devs = devices(cell["chips"])
    kind = devs[0].device_kind
    import peaks
    chip_peaks = peaks.lookup(kind) if devs[0].platform == "tpu" else None

    from compile_stats import CompileStats
    from repro.launch.runtime import float32_matmuls
    harness.start_compile_cache()
    stats = CompileStats()
    timers = Timers()

    with float32_matmuls():
        d = drv.Driver(c.config, c.traffic, args.seed, timers)
        setup_s = time.perf_counter() - T_START
        timers["compile_s"] = stats.compile_s
        say(f"{cell['name']}: set-up {setup_s:.3f} s: " + ", ".join(
            f"{k} {v:.3f}" for k, v in timers.items())
            + f"; programs compiled {stats.compiles}, loaded from the "
            f"persistent cache {stats.cache_hits}")
        built = stats.programs_built()
        win = d.window(args.seconds)
        in_window = stats.programs_built() - built
        say(f"{cell['name']}: programs compiled or loaded inside the "
            f"window: {in_window}")
        trace = None
        if args.trace:
            trace = traced(d, drv, c.traffic["trace_seconds"], cell["name"])
        mem = harness.memory_peak_bytes(devs, d.program_bytes())
    d.release()
    checks = {k: {"value": v, "limit": c.limits[k]}
              for k, v in d.check().items() if k in c.limits}
    correct = all(x["value"] <= x["limit"] for x in checks.values())

    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs), "memory_peak_bytes": mem}
    if args.trace:
        ctx = {"kind": drv.KIND, "setup": dict(timers), "window": win,
               "trace": trace, "peaks": chip_peaks,
               "flops_per_step": getattr(d, "flops_per_step", None)}
        metrics = {}
        for m in harness.cell_metrics(spec, cell["name"], "per_layer"):
            value = harness.metric_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device.update(busy_s=trace["busy_s"], window_s=trace["window_s"])
    else:
        values = dict(win["metrics"], setup_s=setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in harness.cell_metrics(spec, cell["name"],
                                                 "end_to_end")}
    out = {"correct": correct, "attempted": win["attempted"],
           "failed": win["failed"], "metrics": metrics, "device": device,
           "compiles_in_window": in_window}
    if getattr(d, "schedule", None):
        out["schedule"] = d.schedule
    if args.trace:
        out["breakdown"] = {"device_ops": trace["device_ops"],
                            "idle_gaps": trace["idle_gaps"]}
    out["checks"] = checks
    return out


def traced(d, drv, seconds: float, name: str) -> dict:
    """Trace ``seconds`` more of the cell and reduce the trace."""
    import jax
    import trace_reduce

    path = os.path.join(harness.TRACE_DIR, name)
    shutil.rmtree(path, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(path, profiler_options=opts)
    try:
        d.traced_window(seconds, jax.profiler.TraceAnnotation)
    finally:
        jax.profiler.stop_trace()
    try:
        return trace_reduce.reduce(*trace_reduce.load(path, drv.HOST_SPANS))
    finally:
        shutil.rmtree(path, ignore_errors=True)


def main(argv=None) -> int:
    args = parse(argv)
    try:
        out = run(args)
    except harness.BenchError as e:
        say(f"run.py: {e}")
        return 2
    print(json.dumps(out), flush=True)
    for name, c in out["checks"].items():
        verdict = "ok" if c["value"] <= c["limit"] else "FAILED"
        say(f"check {name}: {c['value']!r} (limit {c['limit']!r}) {verdict}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
