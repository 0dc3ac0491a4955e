"""The program's own spans and name scopes in a ``jax.profiler`` trace.

While ``repro.obs`` is enabled, every ``obs.span`` of the program is also a
profiler annotation on the host plane of the trace, carrying its category
as the stat ``cat`` (and its args where ``profile_args`` asked for them);
the compiled steps' ops carry their name scopes (``layer0/aggregate``,
``update``, ``optimizer``) in their ``op_name`` metadata.  :func:`load`
reads one trace into a :class:`Trace` of plain lists, and :func:`reduce`
works on that alone, so that a test can hand it a built trace.

:func:`reduce` returns the keys of ``trace_reduce.reduce`` (computed by it,
from the device ops and the harness's spans only), and:

* ``program_spans``: per span name, the spans begun in the window: count,
  total and self seconds (self: less the program spans nested inside);
* ``span_instances``: per name of a span that carries args, one entry per
  span begun in the window: its seconds, its args, and ``within``, the
  seconds of the program spans nested in it, by name;
* ``device_scopes``: device seconds in the window by the ops' scope path
  (``layer1/aggregate``; ``""`` for ops outside any scope), averaged over
  the chips;
* ``harness_spans``: the number of each harness span begun in the window;
* ``program_idle_gaps``: the device's idle time in the window, summed by
  the innermost program span open on the host at each gap's middle; where
  none was, by the innermost runtime event open then on any host thread,
  under its name in the trace; else ``idle``.  The ten largest, then
  ``other`` for the rest.

:func:`traced` runs a driver's traced window with the program's
instrumentation on and returns that reduction with the registry's snapshot;
run as a script, this file does so for one cell and prints one JSON line:

    python3 benchmarks/chip/program_trace.py --workload gcn-cora.train \\
        --seed 7 --seconds 5 [--keep DIR]
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
import shutil
import sys
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np  # noqa: E402

import trace_reduce  # noqa: E402

TOP = trace_reduce.TOP
_WRAPPER = re.compile(r"^[\w-]+\((.*)\)$")   # jvp(...), transpose(...)


class Op(NamedTuple):
    name: str
    start: float          # ns
    dur: float            # ns
    scope: str            # "layer0/aggregate"; "" outside any scope


class HostEvent(NamedTuple):
    name: str
    start: float          # ns
    dur: float            # ns
    thread: int
    args: Optional[dict]  # a program span's stats (incl. "cat"), else None


@dataclasses.dataclass
class Trace:
    device: Dict[str, List[Op]]
    host: List[HostEvent]
    window: Tuple[float, float]


def scope_path(op_name: str) -> str:
    """The name scopes in an op's ``op_name`` metadata, without the jitted
    functions, the transformation wrappers and the op itself:
    ``"jit(step)/transpose(jvp(layer1))/aggregate/scatter-add"`` gives
    ``"layer1/aggregate"``."""
    parts = []
    for comp in op_name.split("/")[:-1]:
        m = _WRAPPER.match(comp)
        while m and not comp.startswith("jit("):
            comp = m.group(1)
            m = _WRAPPER.match(comp)
        if comp and not m:
            parts.append(comp)
    return "/".join(parts)


def is_program(ev: HostEvent) -> bool:
    return ev.args is not None and "cat" in ev.args


# ------------------------------------------------------------------ reduce
def _innermost(events: Sequence[HostEvent]):
    """Function from a time to the innermost (shortest) event open then."""
    names = [e.name for e in events]
    start = np.array([e.start for e in events], np.float64)
    dur = np.array([e.dur for e in events], np.float64)

    def at(t: float) -> Optional[str]:
        hit = np.flatnonzero((start <= t) & (t < start + dur))
        return names[hit[np.argmin(dur[hit])]] if hit.size else None
    return at


def _nesting(spans: List[HostEvent]):
    """For each span (by index) on its thread: the spans nested inside it
    (all depths), and its parent (the innermost span it is nested in)."""
    inside: Dict[int, List[int]] = collections.defaultdict(list)
    parent: Dict[int, int] = {}
    by_thread: Dict[int, List[int]] = collections.defaultdict(list)
    for i, e in enumerate(spans):
        by_thread[e.thread].append(i)
    for idx in by_thread.values():
        idx.sort(key=lambda i: (spans[i].start, -spans[i].dur))
        stack: List[int] = []
        for i in idx:
            e = spans[i]
            while stack and (spans[stack[-1]].start + spans[stack[-1]].dur
                             <= e.start):
                stack.pop()
            for j in stack:
                inside[j].append(i)
            if stack:
                parent[i] = stack[-1]
            stack.append(i)
    return inside, parent


def reduce(trace: Trace, harness_names: Sequence[str]) -> dict:
    """The keys of the module docstring; ``harness_names`` are the driver's
    ``HOST_SPANS``."""
    lo, hi = trace.window
    harness = set(harness_names) | {trace_reduce.WINDOW}
    device_plain = {chip: [(o.name, o.start, o.dur) for o in ops]
                    for chip, ops in trace.device.items()}
    out = trace_reduce.reduce(
        device_plain, [(e.name, e.start, e.dur) for e in trace.host
                       if e.name in harness and
                       e.name != trace_reduce.WINDOW], trace.window)

    program = [e for e in trace.host if is_program(e)]
    runtime = [e for e in trace.host
               if e.name not in harness and not is_program(e)]

    # program spans begun in the window
    inside, parent = _nesting(program)
    children_ns: Dict[int, float] = collections.Counter()
    for i, j in parent.items():
        children_ns[j] += program[i].dur
    spans: Dict[str, Dict[str, float]] = {}
    instances: Dict[str, List[dict]] = collections.defaultdict(list)
    for i, e in enumerate(program):
        if not lo <= e.start < hi:
            continue
        s = spans.setdefault(e.name, {"count": 0, "total_s": 0.0,
                                      "self_s": 0.0})
        s["count"] += 1
        s["total_s"] += e.dur * 1e-9
        s["self_s"] += (e.dur - children_ns[i]) * 1e-9
        args = {k: v for k, v in e.args.items() if k != "cat"}
        if args:
            within: Dict[str, float] = collections.Counter()
            for j in inside.get(i, ()):
                within[program[j].name] += program[j].dur * 1e-9
            instances[e.name].append({"s": e.dur * 1e-9, "args": args,
                                      "within": dict(within)})
    out["program_spans"] = spans
    out["span_instances"] = dict(instances)

    # device time by scope path, averaged over chips
    scopes: Dict[str, float] = collections.Counter()
    for chip, ops in trace.device.items():
        for o in ops:
            s2, e2 = max(o.start, lo), min(o.start + o.dur, hi)
            if e2 > s2:
                scopes[o.scope] += (e2 - s2) * 1e-9 / len(trace.device)
    out["device_scopes"] = dict(scopes)
    out["harness_spans"] = dict(collections.Counter(
        e.name for e in trace.host
        if e.name in harness and e.name != trace_reduce.WINDOW
        and lo <= e.start < hi))

    # idle gaps of the first chip by program span, else runtime event
    chip0 = sorted(trace.device)[0]
    merged = trace_reduce._merge(
        [(s, e) for _, s, e in trace_reduce._clip(device_plain[chip0], lo,
                                                  hi)])
    edges = [lo] + [t for iv in merged for t in iv] + [hi]
    by_program, by_runtime = _innermost(program), _innermost(runtime)
    gaps: Dict[str, float] = collections.Counter()
    for g0, g1 in zip(edges[0::2], edges[1::2]):
        if g1 > g0:
            mid = (g0 + g1) / 2
            label = by_program(mid) or by_runtime(mid) or "idle"
            gaps[label] += (g1 - g0) * 1e-9
    ranked = sorted(gaps.items(), key=lambda kv: -kv[1])
    top = [[n, t] for n, t in ranked[:TOP]]
    if len(ranked) > TOP:
        top.append(["other", sum(t for _, t in ranked[TOP:])])
    out["program_idle_gaps"] = top
    return out


# -------------------------------------------------------------------- load
_HLO_OP = re.compile(r'^\s*(?:ROOT\s+)?%([\w.\-]+) = [^\n]*?'
                     r'metadata=\{[^}\n]*?op_name="([^"]*)"', re.M)


def hlo_scopes(hlo_text: str) -> Dict[str, str]:
    """Scope path of each instruction of a compiled program, from its
    ``op_name`` metadata: the TPU trace names a device op by its
    instruction and carries no ``op_name`` of its own."""
    return {name: scope_path(op) for name, op in _HLO_OP.findall(hlo_text)}


def _instruction(event_name: str) -> str:
    """``"%fusion.3 = f32[2708,16]{...} fusion(...)"`` gives ``fusion.3``."""
    if event_name.startswith("%") and " = " in event_name:
        return event_name[1:].split(" = ", 1)[0]
    return event_name


def load(trace_dir: str, scopes: Optional[Dict[str, str]] = None) -> Trace:
    """Read the newest ``*.xplane.pb`` under ``trace_dir``: every device op
    with its scope path (from ``scopes``, by instruction name; ``""``
    without), every host event with its thread, and the window."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no trace under {trace_dir}")
    pd = ProfileData.from_file(files[-1])
    scopes = scopes or {}
    device: Dict[str, List[Op]] = {}
    host: List[HostEvent] = []
    thread = 0
    for plane in pd.planes:
        if plane.name.startswith(trace_reduce.DEVICE_PREFIX):
            ops = [Op(trace_reduce.op_name(e.name), float(e.start_ns),
                      float(e.duration_ns),
                      scopes.get(_instruction(e.name), ""))
                   for line in plane.lines
                   if line.name in trace_reduce.OP_LINES
                   for e in line.events]
            if ops:
                device[plane.name] = ops
            continue
        for line in plane.lines:
            thread += 1
            for e in line.events:
                st = dict(e.stats)
                host.append(HostEvent(e.name, float(e.start_ns),
                                      float(e.duration_ns), thread,
                                      st if "cat" in st else None))
    windows = [(e.start, e.start + e.dur) for e in host
               if e.name == trace_reduce.WINDOW]
    if not windows:
        raise ValueError(f"trace holds no {trace_reduce.WINDOW!r} span")
    return Trace(device, host, windows[-1])


# ------------------------------------------------------------------ traced
def step_scopes(d) -> Dict[str, str]:
    """Instruction scopes of a training driver's compiled step (none for a
    driver without one)."""
    lower = getattr(getattr(d, "step", None), "lower", None)
    if lower is None:
        return {}
    return hlo_scopes(lower(d.params, d.opt_state, d.batch).compile()
                      .as_text())


def traced(d, drv, seconds: float, path: str, keep: bool = False) -> dict:
    """Trace ``seconds`` of a driver's traced window with the program's
    instrumentation on; returns the reduction and, under ``registry``, the
    program's registry snapshot of that window."""
    import jax
    from repro import obs

    shutil.rmtree(path, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    obs.reset()
    obs.enable()
    jax.profiler.start_trace(path, profiler_options=opts)
    try:
        d.traced_window(seconds, jax.profiler.TraceAnnotation)
    finally:
        jax.profiler.stop_trace()
        obs.disable()
    snap = obs.snapshot()
    try:
        out = reduce(load(path, step_scopes(d)), drv.HOST_SPANS)
    finally:
        if not keep:
            shutil.rmtree(path, ignore_errors=True)
    out["registry"] = snap
    return out


READERS = ("train.aggregate_ms", "serve.queue_ms", "serve.stall_ms",
           "device_idle.train", "device_idle.serve")


def main(argv=None) -> int:
    import argparse
    import json

    import harness
    import run

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True,
                    help="untraced window run first, as run.py does")
    ap.add_argument("--keep", default=None,
                    help="keep the raw trace in this directory")
    ap.add_argument("--out", default=None,
                    help="write the whole reduction here as JSON; standard "
                    "output leaves out span_instances")
    args = ap.parse_args(argv)
    c = harness.open_cell(args.workload)
    devs = harness.require_devices(c.entry["chips"])
    from compile_stats import CompileStats
    from repro.launch.runtime import float32_matmuls
    harness.start_compile_cache()
    stats = CompileStats()
    with float32_matmuls():
        d = c.driver.Driver(c.config, c.traffic, args.seed, run.Timers())
        d.window(args.seconds)
        built = stats.programs_built()
        path = args.keep or os.path.join(harness.TRACE_DIR, args.workload)
        tr = traced(d, c.driver, c.traffic["trace_seconds"], path,
                    keep=args.keep is not None)
        in_window = stats.programs_built() - built
    ctx = {"kind": c.driver.KIND, "trace": tr, "registry": tr["registry"]}
    metrics = {}
    for name in READERS:
        value = harness.metric_reader(name)(ctx)
        if value is not None:
            metrics[name] = value
    line = {"workload": args.workload, "device": devs[0].device_kind,
            "compiles_in_window": in_window, "metrics": metrics, "trace": tr}
    if args.out:
        with open(args.out, "w") as f:
            json.dump(line, f)
    tr.pop("span_instances")
    tr.pop("registry")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
