"""From a ``jax.profiler`` trace to device busy time, idle share and gaps.

The harness wraps the traced window in a host span named ``WINDOW`` and
each phase it drives in spans of its own (``train.dispatch``,
``serve.process_batch``, ...).  :func:`load` reads the trace file into
plain event lists; :func:`reduce` works on those lists alone, so that a
test can hand it a recorded or hand-built trace.

* busy: the union of the intervals in which a device operation ran, clipped
  to the window, in seconds, averaged over the chips;
* idle share: 1 - busy / window;
* device_ops: the operations that took the most device time, summed by name;
* idle_gaps: the device's idle time inside the window, summed by the
  innermost harness span that was open on the host at the middle of each gap
  (``idle`` where none was).
"""
from __future__ import annotations

import collections
import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

Event = Tuple[str, float, float]          # (name, start_ns, duration_ns)

WINDOW = "bench.window"
DEVICE_PREFIX = "/device:TPU:"
OP_LINES = ("XLA Ops",)                    # TPU planes: one event per op
TOP = 10


def _merge(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(events: Sequence[Event], lo: float, hi: float):
    for name, s, d in events:
        s2, e2 = max(s, lo), min(s + d, hi)
        if e2 > s2:
            yield name, s2, e2


def op_name(text: str) -> str:
    """A device op's name and output type from its HLO text in the trace:
    ``"%fusion.3 = f32[2708,16]{1,0:T(8,128)} fusion(...)"`` gives
    ``"fusion.3 f32[2708,16]"``; other names are kept as they are."""
    if not text.startswith("%") or " = " not in text:
        return text
    name, rest = text[1:].split(" = ", 1)
    return f"{name} {rest.split('{', 1)[0].split(' ', 1)[0]}"


def _labeller(host: Sequence[Event]):
    """Function from a time to the innermost (shortest) harness span open
    at that time."""
    spans = [h for h in host if h[0] != WINDOW]
    names = [n for n, _, _ in spans]
    start = np.array([s for _, s, _ in spans], np.float64)
    dur = np.array([d for _, _, d in spans], np.float64)

    def label(t: float) -> str:
        hit = np.flatnonzero((start <= t) & (t < start + dur))
        return names[hit[np.argmin(dur[hit])]] if hit.size else "idle"
    return label


def reduce(device: Dict[str, Sequence[Event]], host: Sequence[Event],
           window: Tuple[float, float]) -> dict:
    """``device`` maps each chip to its op events; ``host`` holds the
    harness's spans; ``window`` is (start_ns, end_ns)."""
    lo, hi = window
    if not device or hi <= lo:
        raise ValueError("trace holds no device or no window")
    busy_ns, per_op = [], collections.Counter()
    gaps_by_label: Dict[str, float] = collections.Counter()
    label = _labeller(host)
    for i, chip in enumerate(sorted(device)):
        clipped = list(_clip(device[chip], lo, hi))
        merged = _merge([(s, e) for _, s, e in clipped])
        busy_ns.append(sum(e - s for s, e in merged))
        for name, s, e in clipped:
            per_op[name] += e - s
        if i == 0:
            edges = [lo] + [t for iv in merged for t in iv] + [hi]
            for g0, g1 in zip(edges[0::2], edges[1::2]):
                if g1 > g0:
                    gaps_by_label[label((g0 + g1) / 2)] += g1 - g0
    window_s = (hi - lo) * 1e-9
    busy_s = sum(busy_ns) / len(busy_ns) * 1e-9
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "idle_share": 1.0 - busy_s / window_s,
        "device_ops": [[n, t * 1e-9] for n, t in per_op.most_common(TOP)],
        "idle_gaps": [[n, t * 1e-9] for n, t in
                      sorted(gaps_by_label.items(), key=lambda kv: -kv[1])
                      [:TOP]],
    }


def load(trace_dir: str, host_names: Optional[Sequence[str]] = None):
    """Read the newest ``*.xplane.pb`` under ``trace_dir``: returns the
    device op events by chip, the harness's host spans (those named in
    ``host_names``, and the window), and the window's (start, end)."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no trace under {trace_dir}")
    pd = ProfileData.from_file(files[-1])
    keep = set(host_names or ()) | {WINDOW}
    device: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            evs = [(op_name(e.name), float(e.start_ns),
                    float(e.duration_ns))
                   for line in plane.lines if line.name in OP_LINES
                   for e in line.events]
            if evs:
                device[plane.name] = evs
        else:
            host.extend((e.name, float(e.start_ns), float(e.duration_ns))
                        for line in plane.lines for e in line.events
                        if e.name in keep)
    windows = [(s, s + d) for n, s, d in host if n == WINDOW]
    if not windows:
        raise ValueError(f"trace holds no {WINDOW!r} span")
    return device, [h for h in host if h[0] != WINDOW], windows[-1]
