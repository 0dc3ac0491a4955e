"""Measure, don't guess: pick the aggregation engine by wall-clock.

``choose_block_shape`` (core/blocksparse.py) sizes tiles from a VMEM budget
without ever running anything.  This module replaces that heuristic with a
micro-benchmark: for each candidate ``(backend, bm, bk, compact)`` it builds
a :class:`GraphExecutionPlan`, times a jitted **forward + backward** pass
(the training hot path, via ``jax.vjp``), and keeps the winner.  Verdicts are
cached on disk keyed by a structural *graph fingerprint* plus the feature
width, plan mode, and JAX backend, so a graph is only ever tuned once per
machine — later sessions (and later PRs) pick an executor by measurement.

``autotune_layer`` extends the same machinery to WHOLE LAYERS (ISSUE 4): the
candidate space becomes the joint ``(order, fuse, backend, bm, compact)``
grid over a :class:`repro.exec.LayerExecutionPlan` — computation order
(aggregate-then-update vs update-then-aggregate) and one-launch kernel
fusion are tuned together with the aggregation engine, in the same
fingerprinted disk cache.  The FLOP/byte model (:func:`repro.exec.plan.
choose_order`) supplies the prior; the measurement validates or overrules it
and the record keeps both verdicts.

Cache keys carry a **device signature** (:func:`device_sig` — the JAX
backend plus ``jax.devices()[0].device_kind``), so a verdict measured on one
accelerator generation is never silently reused on another (TPU v4 and v5
get distinct keys).  Where the device kind merely repeats the backend name
(CPU), the signature collapses to the bare backend, so pre-existing entries
keyed the old way remain valid there; entries from other devices simply miss
and are re-measured, then age out of the pruned document.

Every trial runs under a :mod:`repro.obs` span (``exec.autotune.trial`` with
backend/bm/compact — and order/fuse for layer trials — attributes, plus the
measured microseconds and the ``traffic_model`` modeled HBM bytes per
launch), and cache hits/misses are counted, so a trace of a tuning run shows
exactly where the budget went.

Cache location: ``$REPRO_EXEC_CACHE`` or ``~/.cache/repro/exec``.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import re
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from .. import obs
from ..graph.structure import Graph
from .bucketing import (bucket_candidates, bucket_layer_candidates,
                        make_layer_cand, split_graph_cand, split_layer_cand)
from .plan import (GraphExecutionPlan, LayerExecutionPlan, build_plan,
                   build_layer_plan, choose_order, layer_order_costs,
                   spmm_cost)

# (backend, bm==bk, compact) — degree-bucketed variants append a non-empty
# bucket signature ("64@8+256", see repro.exec.bucketing) as a 4th element;
# unbucketed candidates stay exact 3-tuples so cache keys never shift
Candidate = Tuple[str, int, bool]
# (order, fuse, backend, bm==bk, compact[, buckets]) — the joint layer space
LayerCandidate = Tuple[str, bool, str, int, bool]

_BYTES_PER_EL = 4

# calibration-guided pruning (ISSUE 9 satellite): skip racing candidates
# whose calibrated predicted cost exceeds PRUNE_ALPHA x the best calibrated
# prediction — the bucketed search space is larger, the trial budget is not
PRUNE_ALPHA = 4.0


def _prune_candidates(cands: list, model_costs: dict, alpha: Optional[float],
                      cache_dir: Optional[str]) -> list:
    """Drop candidates the *calibrated* model predicts can't come close.

    Only candidates whose calibration class carries a measured ratio
    participate: unknown classes are always raced — the uncalibrated model
    alone is exactly what the audit keeps catching misranking, so it never
    gets to veto a candidate on its own.  No calibration table (or fewer
    than two calibrated candidates) disables pruning entirely.
    """
    if alpha is None or len(cands) <= 1:
        return cands
    try:
        from ..obs.audit import cand_class, class_ratios, load_calibration
        ratios = class_ratios(load_calibration(device_sig(), cache_dir))
    except Exception:
        return cands
    calibrated = {}
    for c in cands:
        r = ratios.get(cand_class(c))
        if r is not None:
            calibrated[c] = model_costs[c] * r
    if len(calibrated) < 2:
        return cands
    best = min(calibrated.values())
    kept = []
    pruned = 0
    for c in cands:
        if c in calibrated and calibrated[c] > alpha * best:
            pruned += 1
            continue
        kept.append(c)
    if pruned:
        obs.counter("exec.autotune.pruned").inc(pruned)
    return kept


# ------------------------------------------------- cold cost model (shared)
def model_graph_cost(n: int, e: int, d: int) -> float:
    """Cold-model cost (byte-equivalents) of one aggregation-only launch —
    the modeled counterpart every graph-plan trial is audited against."""
    return spmm_cost(n, e, d)


def model_layer_cost_dims(n: int, e: int, d_in: int, d_out: int,
                          cand: LayerCandidate) -> float:
    """Cold-model cost (byte-equivalents) of one (layer, candidate), from
    plain dimensions.  Extends :func:`repro.exec.plan.layer_order_costs`
    with the fusion credit: the one-launch epilogue keeps the ``(n, d_in)``
    aggregation in VMEM instead of round-tripping it through HBM.  The self
    half's matmul is candidate-independent, so it never moves the argmin and
    is left out.  (:func:`repro.exec.forward.model_layer_cost` is the
    LayerSpec-shaped wrapper.)"""
    order, fuse = cand[0], cand[1]
    cost = layer_order_costs(n, e, d_in, d_out)[order]
    if fuse:
        cost -= 2.0 * n * d_in * _BYTES_PER_EL
    return cost


def default_candidates(platform: Optional[str] = None) -> List[Candidate]:
    """Candidate grid per platform.  On TPU the MXU wants 128-aligned tiles;
    on CPU small tiles keep the dense-tile FLOP overhead near nnz, and the
    fused coo pass is always in the running."""
    platform = platform or jax.default_backend()
    if platform == "tpu":
        return [("pallas", 128, True), ("pallas", 128, False),
                ("pallas", 256, True), ("coo", 128, True)]
    return [("coo", 128, True),
            ("jnp", 16, True), ("jnp", 32, True), ("jnp", 64, True),
            ("jnp", 128, True), ("jnp", 128, False)]


def _device_kind() -> str:
    """``device_kind`` of device 0 (monkeypatchable in tests), tolerant."""
    try:
        return jax.devices()[0].device_kind
    except Exception:
        return "unknown"


def device_sig(platform: Optional[str] = None) -> str:
    """Backend + device-kind cache-key component, e.g. ``"tpu-TPU-v4"``.

    Collapses to the bare backend name when the device kind just repeats it
    (CPU: kind ``"cpu"`` on backend ``"cpu"``), which keeps old entries
    valid there; everywhere else the kind distinguishes accelerator
    generations, so verdicts never migrate across device kinds silently.
    """
    platform = platform or jax.default_backend()
    kind = re.sub(r"[^A-Za-z0-9._-]+", "-", _device_kind().strip())
    if kind.lower() == platform.lower() or kind == "unknown":
        return platform
    return f"{platform}-{kind}"


def graph_fingerprint(g: Graph) -> str:
    """Structural hash: node/edge counts + exact edge list + mask."""
    h = hashlib.sha1()
    h.update(np.int64(g.num_nodes).tobytes())
    h.update(np.ascontiguousarray(g.src.astype(np.int64)).tobytes())
    h.update(np.ascontiguousarray(g.dst.astype(np.int64)).tobytes())
    if g.edge_mask is not None:
        h.update(np.packbits(g.edge_mask).tobytes())
    return h.hexdigest()[:16]


@dataclasses.dataclass(frozen=True)
class AutotuneRecord:
    key: str
    backend: str
    bm: int
    compact: bool
    us: float                      # winner's fwd+bwd microseconds
    table: Tuple[Tuple, ...]       # all measurements (bucketed rows carry
    from_cache: bool               # their signature before ``us``)
    buckets: str = ""              # winner's bucket signature ("" = single)

    def as_config(self) -> dict:
        return {"backend": self.backend, "bm": self.bm, "bk": self.bm,
                "compact": self.compact, "buckets": self.buckets}


# ------------------------------------------------------------------- cache
CACHE_MAX_ENTRIES = 1024      # prune_cache keeps the most recently written


def _cache_path(cache_dir: Optional[str]) -> str:
    root = cache_dir or os.environ.get(
        "REPRO_EXEC_CACHE",
        os.path.join(os.path.expanduser("~"), ".cache", "repro", "exec"))
    return os.path.join(root, "autotune.json")


def _cache_load(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return {}


def _cache_store(path: str, entries: dict) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(entries, f, indent=1, sort_keys=True)
    os.replace(tmp, path)


def _cache_put(path: str, key: str, value: dict,
               max_entries: Optional[int] = None) -> None:
    """Insert one entry (re-reading first so concurrent tuners of OTHER keys
    aren't clobbered — per-key last-write wins), stamp its write time, and
    prune the document to ``max_entries`` most-recently-written keys so the
    file can't grow without bound across graph fingerprints."""
    entries = _cache_load(path)
    value = dict(value)
    value["_ts"] = time.time()
    entries[key] = value
    _prune(entries, max_entries if max_entries is not None
           else CACHE_MAX_ENTRIES)
    _cache_store(path, entries)


def _prune(entries: dict, max_entries: int) -> None:
    if len(entries) <= max_entries:
        return
    # unstamped entries predate the stamp and are evicted first
    victims = sorted(entries, key=lambda k: entries[k].get("_ts", 0.0),
                     reverse=True)[max_entries:]
    for k in victims:
        del entries[k]


def prune_cache(max_entries: int = CACHE_MAX_ENTRIES,
                cache_dir: Optional[str] = None) -> int:
    """Trim the autotune disk cache to its ``max_entries`` most-recently-
    written keys; returns the number of entries remaining.  Every store
    already prunes, so this is only needed to shrink an existing file."""
    path = _cache_path(cache_dir)
    entries = _cache_load(path)
    _prune(entries, max_entries)
    try:
        _cache_store(path, entries)
    except OSError:
        pass
    return len(entries)


# ------------------------------------------------------------- quarantine
def quarantine_key(fingerprint: str, backend: str,
                   platform: Optional[str] = None) -> str:
    return f"{fingerprint}:quarantine:{backend}:{device_sig(platform)}"


def record_quarantine(fingerprint: str, backend: str, *, reason: str = "",
                      platform: Optional[str] = None,
                      cache_dir: Optional[str] = None) -> None:
    """Persist a "this backend failed on this graph" verdict next to the
    autotune entries (:mod:`repro.exec.fallback` writes one when a launch
    raises or flunks the parity probe), so every later scheduler on this
    device — the DP oracle included — stops choosing the backend."""
    obs.counter("exec.quarantine", backend=backend).inc()
    obs.instant("exec.quarantine", cat="exec", backend=backend,
                reason=reason, fingerprint=fingerprint)
    try:
        _cache_put(_cache_path(cache_dir),
                   quarantine_key(fingerprint, backend, platform),
                   {"quarantined": True, "reason": reason})
    except OSError:
        pass              # read-only FS: the in-process fallback still held


def quarantined_backends(fingerprint: str, *,
                         platform: Optional[str] = None,
                         cache_dir: Optional[str] = None) -> set:
    """The backends quarantined for this graph on this device."""
    prefix = f"{fingerprint}:quarantine:"
    suffix = f":{device_sig(platform)}"
    out = set()
    for key, e in _cache_load(_cache_path(cache_dir)).items():
        if (key.startswith(prefix) and key.endswith(suffix)
                and isinstance(e, dict) and e.get("quarantined")):
            out.add(key[len(prefix):len(key) - len(suffix)])
    return out


def clear_quarantine(fingerprint: str, *, platform: Optional[str] = None,
                     cache_dir: Optional[str] = None) -> int:
    """Lift every quarantine for this graph on this device (e.g. after a
    driver upgrade); returns how many verdicts were removed."""
    path = _cache_path(cache_dir)
    entries = _cache_load(path)
    victims = [quarantine_key(fingerprint, b, platform)
               for b in quarantined_backends(fingerprint, platform=platform,
                                             cache_dir=cache_dir)]
    for k in victims:
        entries.pop(k, None)
    if victims:
        try:
            _cache_store(path, entries)
        except OSError:
            pass
    return len(victims)


def cached_layer_costs(g: Graph, d_in: int, d_out: int, mode: str = "gcn", *,
                       relu: bool = True, bias: bool = True,
                       platform: Optional[str] = None,
                       cache_dir: Optional[str] = None
                       ) -> Dict[LayerCandidate, float]:
    """Measured fwd+bwd microseconds per layer candidate, merged from every
    cached :func:`autotune_layer` run of this (graph, shape, mode, epilogue)
    on this platform — regardless of which candidate SET each run raced.
    The whole-forward DP (:mod:`repro.exec.forward`) uses this as its warm
    per-edge cost oracle; an empty dict means the layer is cold."""
    prefix = (f"{graph_fingerprint(g)}:layer:{d_in}x{d_out}:{mode}:"
              f"r{int(relu)}b{int(bias)}:{device_sig(platform)}:")
    out: Dict[LayerCandidate, float] = {}
    for key, e in _cache_load(_cache_path(cache_dir)).items():
        if not key.startswith(prefix) or not isinstance(e, dict):
            continue
        rows = e.get("table", ())
        if not isinstance(rows, (list, tuple)):
            obs.counter("exec.autotune.cache", result="corrupt").inc()
            continue
        for row in rows:
            # a corrupt row is skipped, never allowed to poison the DP
            try:
                if len(row) == 7:          # degree-bucketed layer trial
                    order, fuse, backend, bm, compact, bsig, us = row
                else:
                    order, fuse, backend, bm, compact, us = row
                    bsig = ""
                cand = make_layer_cand(str(order), bool(fuse), str(backend),
                                       int(bm), bool(compact), str(bsig))
                us = float(us)
            except (TypeError, ValueError):
                obs.counter("exec.autotune.cache", result="corrupt").inc()
                continue
            if cand not in out or us < out[cand]:
                out[cand] = us
    return out


# --------------------------------------------------------------- measuring
def _modeled_traffic(plan: GraphExecutionPlan, d: int) -> dict:
    """Modeled HBM bytes per launch for a trial span — only when the plan
    already carries a block-ELL layout (coo plans build it lazily; forcing
    the build just to annotate a span would be paying for the telemetry)."""
    if not obs.enabled() or getattr(plan, "_ell", None) is None:
        return {}
    try:
        t = plan.traffic(d)
        return {"modeled_gather_bytes": int(t["gather_bytes"]),
                "modeled_blockell_bytes": int(t["blockell_bytes"])}
    except Exception:
        return {}


def _report_failure(sp, backend: str, cand: Sequence,
                    err: BaseException) -> None:
    """A candidate that fails to build, compile or run loses the race, but
    never silently: the failure is counted per backend (ungated, so it
    shows even with telemetry off), put on the trial span, and printed
    with the exception text."""
    obs.counter("exec.autotune.failed", gated=False, backend=backend).inc()
    sp.set(failed=True, error=type(err).__name__)
    print(f"autotune: candidate {tuple(cand)} failed: "
          f"{type(err).__name__}: {err}")


def _time_fwd_bwd(plan: GraphExecutionPlan, x: jax.Array,
                  iters: int = 3, warmup: int = 1) -> float:
    """Median microseconds of one jitted forward+backward through the plan."""

    @jax.jit
    def step(x):
        y, vjp = jax.vjp(plan.apply, x)
        (dx,) = vjp(y)
        return dx

    for _ in range(warmup):
        jax.block_until_ready(step(x))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(step(x))
        ts.append((time.perf_counter() - t0) * 1e6)
    return float(np.median(ts))


def autotune(g: Graph, d: int, mode: str = "gcn", *,
             candidates: Optional[Sequence[Candidate]] = None,
             cache_dir: Optional[str] = None, force: bool = False,
             iters: int = 3, seed: int = 0, prune: bool = True,
             prune_alpha: float = PRUNE_ALPHA) -> AutotuneRecord:
    """Measure the candidate grid on ``g`` and return the winner (cached).

    With ``candidates=None`` the platform defaults are extended by
    degree-bucketed variants when the graph's degree distribution warrants
    them (:func:`repro.exec.bucketing.bucket_candidates`).  ``prune``
    (opt-out) skips candidates whose calibration-scaled model cost exceeds
    ``prune_alpha`` x the best calibrated candidate; see
    :func:`_prune_candidates` for the safety rules."""
    platform = jax.default_backend()
    if candidates is not None:
        cands = list(candidates)
    else:
        cands = default_candidates(platform) + bucket_candidates(g, platform)
    # the candidate set is part of the key: a cached verdict must never
    # hand back a config the caller explicitly excluded.  (Pruning happens
    # after keying — the key reflects what the caller ASKED to race.)
    cand_sig = hashlib.sha1(repr(sorted(cands)).encode()).hexdigest()[:8]
    key = f"{graph_fingerprint(g)}:{d}:{mode}:{device_sig(platform)}:{cand_sig}"
    path = _cache_path(cache_dir)
    entries = _cache_load(path)
    if not force and key in entries:
        e = entries[key]
        try:      # a corrupt entry is a miss (re-measure), never a crash
            rec = AutotuneRecord(
                key=key, backend=str(e["backend"]), bm=int(e["bm"]),
                compact=bool(e["compact"]), us=float(e["us"]),
                table=tuple(tuple(r) for r in e.get("table", ())),
                from_cache=True, buckets=str(e.get("buckets", "")))
        except (KeyError, TypeError, ValueError, AttributeError):
            obs.counter("exec.autotune.cache", result="corrupt").inc()
        else:
            obs.counter("exec.autotune.cache", result="hit").inc()
            return rec
    obs.counter("exec.autotune.cache", result="miss").inc()

    x = jnp.asarray(np.random.default_rng(seed)
                    .standard_normal((g.num_nodes, d)).astype(np.float32))
    n_nodes, n_edges = g.num_nodes, g.num_valid_edges
    model_cost = model_graph_cost(n_nodes, n_edges, d)
    race = _prune_candidates(cands, {c: model_cost for c in cands},
                             prune_alpha if prune else None, cache_dir)
    table: List[Tuple] = []
    best = None
    for cand in race:
        backend, bm, compact, bsig = split_graph_cand(cand)
        with obs.span("exec.autotune.trial", cat="exec", backend=backend,
                      bm=bm, compact=compact, buckets=bsig, d=d, mode=mode,
                      n=n_nodes, e=n_edges, model_cost=model_cost) as sp:
            try:
                plan = build_plan(g, mode, bm=bm, bk=bm, backend=backend,
                                  compact=compact, buckets=bsig)
                us = _time_fwd_bwd(plan, x, iters=iters)
            except Exception as err:   # the candidate loses, loudly
                _report_failure(sp, backend, cand, err)
                continue
            sp.set(us=us, **_modeled_traffic(plan, d))
        obs.counter("exec.autotune.trials").inc()
        table.append((backend, bm, compact, bsig, us) if bsig
                     else (backend, bm, compact, us))
        if best is None or us < best[0]:
            best = (us, (backend, bm, compact, bsig))
    if best is None:
        raise RuntimeError("autotune: every candidate failed "
                           f"(tried {race})")
    us, (backend, bm, compact, bsig) = best
    try:
        # geometry + device_sig ride along so repro.obs.audit can re-model
        # every table row offline and key the calibration per device
        _cache_put(path, key, {"backend": backend, "bm": bm,
                               "compact": compact, "buckets": bsig,
                               "us": us, "table": table,
                               "n": n_nodes, "e": n_edges, "d": d,
                               "mode": mode,
                               "device_sig": device_sig(platform)})
    except OSError:
        pass                  # read-only FS: tuning still works, just uncached
    return AutotuneRecord(key=key, backend=backend, bm=bm, compact=compact,
                          us=us, table=tuple(table), from_cache=False,
                          buckets=bsig)


def autotune_plan(g: Graph, d: int, mode: str = "gcn", *,
                  candidates: Optional[Sequence[Candidate]] = None,
                  cache_dir: Optional[str] = None, force: bool = False,
                  iters: int = 3) -> Tuple[GraphExecutionPlan, AutotuneRecord]:
    """Autotune then build the winning plan for ``g``."""
    rec = autotune(g, d, mode, candidates=candidates, cache_dir=cache_dir,
                   force=force, iters=iters)
    plan = build_plan(g, mode, bm=rec.bm, bk=rec.bm, backend=rec.backend,
                      compact=rec.compact, buckets=rec.buckets)
    return plan, rec


# ---------------------------------------------------------------------------
# joint layer autotune: (order, fuse, backend, bm, compact) in one space
# ---------------------------------------------------------------------------
def default_layer_candidates(platform: Optional[str] = None,
                             d_in: Optional[int] = None,
                             d_out: Optional[int] = None
                             ) -> List[LayerCandidate]:
    """Joint candidate grid per platform.  ``fuse=True`` (the one-launch
    Pallas layer kernel) only exists for pallas in aggregate-first order; the
    CPU grid races both orders over the coo and jnp engines — but the jnp
    dense-tile engine is width-gated: at a wide feature side (cora's 1433)
    it costs seconds per call and can never win, so racing it would burn the
    whole tuning budget."""
    platform = platform or jax.default_backend()
    if platform == "tpu":
        return [("aggregate_first", True, "pallas", 128, True),
                ("aggregate_first", True, "pallas", 128, False),
                ("aggregate_first", False, "pallas", 128, True),
                ("aggregate_first", True, "pallas", 256, True),
                ("update_first", False, "pallas", 128, True),
                ("update_first", False, "coo", 128, True)]
    cands = [("aggregate_first", False, "coo", 128, True),
             ("update_first", False, "coo", 128, True)]
    if d_in is None or d_in <= 256:
        cands.append(("aggregate_first", False, "jnp", 64, True))
    if d_out is None or d_out <= 256:
        cands.append(("update_first", False, "jnp", 64, True))
    return cands


@dataclasses.dataclass(frozen=True)
class LayerAutotuneRecord:
    key: str
    order: str
    fuse: bool
    backend: str
    bm: int
    compact: bool
    us: float                      # winner's fwd+bwd microseconds
    model_order: str               # what the FLOP/byte model predicted
    table: Tuple[Tuple, ...]       # bucketed rows carry their sig before us
    from_cache: bool
    buckets: str = ""              # winner's bucket signature ("" = single)
    failed: Tuple[Tuple, ...] = ()   # candidates that raised when raced

    @property
    def order_agrees_with_model(self) -> bool:
        return self.order == self.model_order

    def as_config(self) -> dict:
        return {"order": self.order, "fuse": self.fuse,
                "backend": self.backend, "bm": self.bm, "bk": self.bm,
                "compact": self.compact, "buckets": self.buckets}


def _time_layer_fwd_bwd(lp: LayerExecutionPlan, x: jax.Array, w: jax.Array,
                        b: Optional[jax.Array], relu: bool,
                        iters: int = 3, warmup: int = 1) -> float:
    """Median microseconds of one jitted layer forward+backward (x, w, b)."""

    if b is None:
        @jax.jit
        def step(x, w):
            y, vjp = jax.vjp(lambda x, w: lp.apply(x, w, relu=relu), x, w)
            return vjp(y)
    else:
        @jax.jit
        def step(x, w, b):
            y, vjp = jax.vjp(lambda x, w, b: lp.apply(x, w, b, relu=relu),
                             x, w, b)
            return vjp(y)
    args = (x, w) if b is None else (x, w, b)
    for _ in range(warmup):
        jax.block_until_ready(step(*args))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(step(*args))
        ts.append((time.perf_counter() - t0) * 1e6)
    return float(np.median(ts))


def autotune_layer(g: Graph, d_in: int, d_out: int, mode: str = "gcn", *,
                   relu: bool = True, bias: bool = True,
                   candidates: Optional[Sequence[LayerCandidate]] = None,
                   cache_dir: Optional[str] = None, force: bool = False,
                   iters: int = 3, seed: int = 0, prune: bool = True,
                   prune_alpha: float = PRUNE_ALPHA,
                   _gplan_cache: Optional[Dict] = None) -> LayerAutotuneRecord:
    """Measure the joint layer space on ``g`` and return the winner (cached).

    Shares the graph-plan autotune's fingerprinted disk cache; keys carry the
    layer shape, mode, epilogue flags, platform, and candidate signature.
    ``candidates=None`` extends the platform defaults with degree-bucketed
    variants on skewed graphs; ``prune`` (opt-out) applies the
    calibration-guided candidate skip (:func:`_prune_candidates`)."""
    platform = jax.default_backend()
    if candidates is not None:
        cands = list(candidates)
    else:
        cands = (default_layer_candidates(platform, d_in, d_out)
                 + bucket_layer_candidates(g, platform, d_in, d_out))
    cand_sig = hashlib.sha1(repr(sorted(cands)).encode()).hexdigest()[:8]
    model_order = choose_order(g.num_nodes, g.num_valid_edges, d_in, d_out)
    key = (f"{graph_fingerprint(g)}:layer:{d_in}x{d_out}:{mode}:"
           f"r{int(relu)}b{int(bias)}:{device_sig(platform)}:{cand_sig}")
    path = _cache_path(cache_dir)
    entries = _cache_load(path)
    if not force and key in entries:
        e = entries[key]
        try:      # a corrupt entry is a miss (re-measure), never a crash
            rec = LayerAutotuneRecord(
                key=key, order=str(e["order"]), fuse=bool(e["fuse"]),
                backend=str(e["backend"]), bm=int(e["bm"]),
                compact=bool(e["compact"]), us=float(e["us"]),
                model_order=str(e.get("model_order", model_order)),
                table=tuple(tuple(r) for r in e.get("table", ())),
                from_cache=True, buckets=str(e.get("buckets", "")),
                failed=tuple(tuple(c) for c in e.get("failed", ())))
        except (KeyError, TypeError, ValueError, AttributeError):
            obs.counter("exec.autotune.cache", result="corrupt").inc()
        else:
            obs.counter("exec.autotune.cache", result="hit").inc()
            return rec
    obs.counter("exec.autotune.cache", result="miss").inc()

    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((g.num_nodes, d_in))
                    .astype(np.float32))
    w = jnp.asarray((rng.standard_normal((d_in, d_out)) / np.sqrt(d_in))
                    .astype(np.float32))
    b = jnp.asarray(rng.standard_normal(d_out).astype(np.float32)) \
        if bias else None
    gplans: Dict[Tuple, GraphExecutionPlan] = (
        {} if _gplan_cache is None else _gplan_cache)
    n_nodes, n_edges = g.num_nodes, g.num_valid_edges
    model_costs = {c: model_layer_cost_dims(n_nodes, n_edges, d_in, d_out, c)
                   for c in cands}
    race = _prune_candidates(cands, model_costs,
                             prune_alpha if prune else None, cache_dir)
    table: List[Tuple] = []
    failed: List[Tuple] = []
    best = None
    for cand in race:
        order, fuse, backend, bm, compact, bsig = split_layer_cand(cand)
        with obs.span("exec.autotune.trial", cat="exec", backend=backend,
                      bm=bm, compact=compact, order=order, fuse=fuse,
                      buckets=bsig, d_in=d_in, d_out=d_out, mode=mode,
                      n=n_nodes, e=n_edges,
                      model_cost=model_costs[cand]) as sp:
            try:
                gkey = (backend, bm, compact, bsig)
                if gkey not in gplans:
                    gplans[gkey] = build_plan(g, mode, bm=bm, bk=bm,
                                              backend=backend,
                                              compact=compact, buckets=bsig)
                lp = build_layer_plan(g, mode, d_in=d_in, d_out=d_out,
                                      order=order, fuse=fuse,
                                      gplan=gplans[gkey])
                us = _time_layer_fwd_bwd(lp, x, w, b, relu, iters=iters)
            except Exception as err:   # the candidate loses, loudly
                _report_failure(sp, backend, cand, err)
                failed.append(tuple(cand))
                continue
            sp.set(us=us, **_modeled_traffic(gplans[gkey], d_out))
        obs.counter("exec.autotune.trials").inc()
        table.append((order, fuse, backend, bm, compact, bsig, us) if bsig
                     else (order, fuse, backend, bm, compact, us))
        if best is None or us < best[0]:
            best = (us, (order, fuse, backend, bm, compact, bsig))
    if best is None:
        raise RuntimeError("autotune_layer: every candidate failed "
                           f"(tried {race})")
    us, (order, fuse, backend, bm, compact, bsig) = best
    if order != model_order:
        # hysteresis toward the analytic prior: the measurement overrules
        # the FLOP/byte model only when it is decisively (>10%) better —
        # CPU-timer noise must not flip the computation order
        contenders = [r for r in table if r[0] == model_order]
        if contenders:
            alt = min(contenders, key=lambda r: r[-1])
            if alt[-1] <= us * 1.10:
                us = alt[-1]
                order, fuse, backend, bm, compact, bsig = \
                    split_layer_cand(alt[:-1])
    try:
        # geometry + device_sig ride along for repro.obs.audit (see above)
        _cache_put(path, key, {"order": order, "fuse": fuse,
                               "backend": backend, "bm": bm,
                               "compact": compact, "buckets": bsig,
                               "us": us,
                               "model_order": model_order, "table": table,
                               "failed": failed, "n": n_nodes, "e": n_edges,
                               "d_in": d_in, "d_out": d_out, "mode": mode,
                               "device_sig": device_sig(platform)})
    except OSError:
        pass                  # read-only FS: tuning still works, just uncached
    return LayerAutotuneRecord(key=key, order=order, fuse=fuse,
                               backend=backend, bm=bm, compact=compact,
                               us=us, model_order=model_order,
                               table=tuple(table), from_cache=False,
                               buckets=bsig, failed=tuple(failed))


def autotune_layer_plan(g: Graph, d_in: int, d_out: int, mode: str = "gcn",
                        *, relu: bool = True, bias: bool = True,
                        candidates: Optional[Sequence[LayerCandidate]] = None,
                        cache_dir: Optional[str] = None, force: bool = False,
                        iters: int = 3,
                        gplan: Optional[GraphExecutionPlan] = None
                        ) -> Tuple[LayerExecutionPlan, LayerAutotuneRecord]:
    """Autotune the joint space, then build the winning layer plan.

    Pass ``gplan`` to reuse an existing graph plan when it already matches
    the winning (mode, backend, bm, compact, buckets); graph plans built
    during an uncached tuning run are reused too — the winner is never
    reconstructed from scratch."""
    built: Dict[Tuple, GraphExecutionPlan] = {}
    rec = autotune_layer(g, d_in, d_out, mode, relu=relu, bias=bias,
                         candidates=candidates, cache_dir=cache_dir,
                         force=force, iters=iters, _gplan_cache=built)
    win = (rec.backend, rec.bm, rec.compact, rec.buckets)
    if gplan is not None and (
            gplan.mode != mode
            or (gplan.backend, gplan.bm, gplan.compact,
                gplan.buckets) != win):
        gplan = None
    if gplan is None:
        gplan = built.get(win)
    lp = build_layer_plan(g, mode, d_in=d_in, d_out=d_out, order=rec.order,
                          fuse=rec.fuse, bm=rec.bm, bk=rec.bm,
                          backend=rec.backend, compact=rec.compact,
                          gplan=gplan, buckets=rec.buckets)
    return lp, rec
