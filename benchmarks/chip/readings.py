"""The two readings each limit of ``limits/<workload>.json`` is set from.

    python3 benchmarks/chip/readings.py --workload gcn-cora.train \
        --seeds 101,102,103 [--seconds 3]

For each seed, in one process: the cell's set-up (and, for a serving cell,
a window of ``--seconds`` at the cell's load), then the numbers the run
compares with the reference, read three ways:

* ``program``: the program, as a run reads them (the lower reading is the
  largest over sound seeds);
* ``control``: the reference computed one step of precision below the
  configuration's (``reference.matmul_bf16x3``) put in the program's place
  (the upper reading is the smallest over seeds);
* ``half_batch`` (training): the reference with half the training nodes
  left out of the loss, put in the program's place: a planted fault.

A step that leaves the state unchanged reads 1 on ``change_gap`` by
construction and needs no run.  Prints one JSON line per seed, then the
largest program reading and the smallest control and fault readings.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import harness  # noqa: E402
import reference  # noqa: E402


def train_control(drv, d, matmul=reference.matmul_bf16x3, train_mask=None):
    """Readings of the reference at ``matmul`` (on ``train_mask``) in the
    program's place, against the reference as the run computes it; ``d`` is
    a set-up ``drv.Driver``."""
    losses, grad, params = d.reference_steps(matmul, train_mask)
    first_m = [{k: (1 - reference.ADAM_B1) * np.asarray(v)
                for k, v in layer.items()} for layer in grad]
    return drv.readings(losses, first_m, d.params0, params,
                        *d.reference_steps())


def half_mask(d, seed: int) -> np.ndarray:
    """The training mask with a random half of its nodes left out."""
    mask = np.asarray(d.data.train_mask).copy()
    on = np.flatnonzero(mask)
    rng = np.random.default_rng(seed)
    mask[rng.choice(on, size=on.size // 2, replace=False)] = False
    return mask


def serve_control(drv, d, matmul=reference.matmul_bf16x3):
    """Readings of the reference's rows at ``matmul`` served in place of
    the program's answers of ``d``'s window."""
    ctrl = d.reference_rows(matmul)
    run = dict(d.run, answers=ctrl[d.run["nodes"]])
    return drv.readings(run, d.reference_rows())


def read_seed(drv, cfg, traffic, seed: int, seconds: float) -> dict:
    from run import Timers
    d = drv.Driver(cfg, traffic, seed, Timers())
    if drv.KIND == "serve":
        d.window(seconds)
    d.release()
    out = {"seed": seed, "program": d.check()}
    if drv.KIND == "train":
        out["control"] = train_control(drv, d)
        out["half_batch"] = train_control(drv, d, reference.matmul_highest,
                                          half_mask(d, seed))
    else:
        out["control"] = serve_control(drv, d)
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    c = harness.open_cell(args.workload)
    harness.require_devices(c.entry["chips"])
    from repro.launch.runtime import float32_matmuls
    harness.start_compile_cache()
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        with float32_matmuls():
            row = read_seed(c.driver, c.config, c.traffic, seed,
                            args.seconds)
        rows.append(row)
        print(json.dumps(row), flush=True)
    summary = {"workload": args.workload, "seeds": len(rows)}
    for kind, pick in (("program", max), ("control", min),
                       ("half_batch", min)):
        if kind in rows[0]:
            summary[kind] = {k: pick(r[kind][k] for r in rows)
                             for k in rows[0][kind]}
    print(json.dumps(summary), flush=True)


if __name__ == "__main__":
    main()
