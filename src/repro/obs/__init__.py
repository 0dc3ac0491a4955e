"""repro.obs — unified tracing, counters, and profiling across the stack.

Rubik's thesis is that hierarchical graph learning lives or dies on
*measurable* quantities — cache hit rates, off-chip bytes, per-kernel
utilization.  This package is the one instrumentation layer every subsystem
reports through, with the same clock and the same schema:

* :mod:`repro.obs.registry` — process-local counters / gauges / streaming
  histograms (fixed log-spaced buckets, bounded memory, percentile error
  bounded by one bucket ratio).  Gated on a module-level enabled flag; the
  disabled fast path is one attribute load and a branch.
* :mod:`repro.obs.trace`    — ``span()``: while enabled, a
  ``jax.profiler`` annotation (the program's spans and the device ops on
  one clock in a profiler trace, plus a ``host.gc`` span per collection);
  while a tracer is installed, Perfetto / chrome://tracing JSON.  A shared
  no-op singleton while both are off.
* :mod:`repro.obs.export`   — run provenance (git SHA, device kind, jax
  version), the shared event schema benchmarks emit through, and the
  ``--metrics-out FILE.jsonl`` dump.
* :mod:`repro.obs.validate` — schema validators for the emitted files
  (``python -m repro.obs.validate out.jsonl trace.json``), run in CI.
* :mod:`repro.obs.summary`  — terminal one-pager over metrics JSONL +
  traces (``python -m repro.obs.summary out.jsonl trace.json``).
* :mod:`repro.obs.audit`    — joins measured autotune telemetry against the
  exec cold cost model into a per-(backend, bm, compact, order) calibration
  table keyed by ``device_sig`` (consumed by the whole-forward DP) plus a
  drift report of model misranks (``python -m repro.obs.audit``).
* :mod:`repro.obs.regress`  — noise-aware perf-regression gate: bootstrap
  CIs on benchmark sample ratios, ``BENCH_trajectory.jsonl`` store
  (``python -m repro.obs.regress compare BASE.json CURRENT.json``).

Instrumented surfaces: ``exec`` (plan compiles, autotune trials, DP schedule
verdicts, modeled HBM bytes), ``serve`` (batch, session, cache and layer
spans, queue wait, batcher queue depth and flush reasons, per-layer cache
hit rates), ``dist`` (halo bytes/chip,
send/recv plan sizes), ``train`` (step time, rows/sec, executor verdict).
Turn it on with ``obs.enable()`` + ``obs.start_trace()``, or the
``--metrics-out`` / ``--trace`` flags on ``launch/train.py`` and
``launch/serve.py``.
"""
from .registry import (Counter, Gauge, Histogram, Registry, REGISTRY,
                       counter, gauge, histogram, snapshot, to_prometheus,
                       reset, enable, disable, enabled, enabled_scope,
                       full_name)
from .trace import (Tracer, Span, NOOP_SPAN, span, instant, start_trace,
                    stop_trace, tracing, tracing_to, current_tracer)
from .export import (provenance, event, git_sha, device_kind, jax_version,
                     metric_records, dump_metrics_jsonl,
                     add_cli_flags, observed_run,
                     SCHEMA_PROVENANCE, SCHEMA_METRIC, SCHEMA_EVENT)
