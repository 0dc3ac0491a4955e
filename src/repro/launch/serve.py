"""Serving launcher: LM prefill+decode loop, or online graph inference.

LM path (reduced config, CPU-friendly):

  PYTHONPATH=src python -m repro.launch.serve --arch granite-8b --tokens 16

Graph path (repro.serve engine: micro-batcher -> reorder-aware embedding
cache -> sampled forward, oracle-checked against the offline full-graph
forward):

  PYTHONPATH=src python -m repro.launch.serve --graph cora --model gcn \
      --requests 200 --cache-kb 500 --warm reorder
"""
import argparse
import importlib
import time

import numpy as np
import jax
import jax.numpy as jnp

from .. import obs
from ..models import lm_init, lm_prefill, lm_decode_step
from .runtime import enable_compile_cache, float32_matmuls


def serve_lm(args) -> None:
    mod = importlib.import_module(
        "repro.configs." + args.arch.replace("-", "_"))
    cfg = mod.REDUCED
    prompt_len = args.prompt_len
    max_seq = max(64, prompt_len + args.tokens + 1)
    key = jax.random.PRNGKey(0)
    params = lm_init(key, cfg)
    prompt = jax.random.randint(key, (args.batch, prompt_len), 0, cfg.vocab)

    logits, caches = jax.jit(lambda p, t: lm_prefill(p, t, cfg))(params,
                                                                 prompt)
    # pad caches to max_seq on the sequence axis
    def pad(c):
        pads = [(0, 0)] * c.ndim
        pads[-3] = (0, max_seq - c.shape[-3])
        return jnp.pad(c, pads)
    caches = jax.tree_util.tree_map(pad, caches)

    step = jax.jit(lambda p, t, c, n: lm_decode_step(p, t, c, n, cfg,
                                                     max_seq),
                   donate_argnums=(2,))
    tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
    out_tokens = [tok]
    t0 = time.perf_counter()
    for i in range(args.tokens):
        logits, caches = step(params, tok, caches,
                              jnp.int32(prompt_len + i))
        tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        out_tokens.append(tok)
    dt = time.perf_counter() - t0
    seq = np.concatenate([np.asarray(t) for t in out_tokens], axis=1)
    print("generated:", seq[0].tolist())
    print(f"{args.tokens} tokens x {args.batch} batch in {dt:.2f}s "
          f"({args.tokens * args.batch / dt:.1f} tok/s on "
          f"{jax.devices()[0].platform})")


def _load_graph(name: str, scale: float):
    from ..graph import cora_like, citeseer_s_like, reddit_like
    if name == "cora":
        return cora_like(seed=0)
    if name == "citeseer-s":
        return citeseer_s_like(scale=scale, seed=0)
    if name == "reddit":
        return reddit_like(scale=scale, seed=0)
    raise SystemExit(f"unknown --graph {name!r} "
                     "(choices: cora, citeseer-s, reddit)")


def serve_graph(args):
    from ..core import identity_order, minhash_reorder
    from ..serve import (EmbeddingCache, MicroBatcher, ServeEngine,
                         make_session, zipfian_trace)

    g = _load_graph(args.graph, args.scale)
    print(f"graph {args.graph}: {g.num_nodes} nodes, {g.num_edges} edges; "
          f"model={args.model}")
    sess = make_session(args.model, g, seed=0)
    order = (minhash_reorder(g) if args.warm != "index"
             else identity_order(g))
    cache = EmbeddingCache(sess.layer_dims, args.cache_kb * 1024,
                           order=order, line_size=args.line_size,
                           num_nodes=g.num_nodes)
    eng = ServeEngine(sess, cache,
                      MicroBatcher(max_batch=args.max_batch,
                                   max_wait=args.max_wait_ms * 1e-3),
                      oracle_check=not args.no_oracle)
    if args.warm != "none":
        warmed = eng.warm(order)
        print(f"warmed {warmed} entries along {args.warm} order")
    trace = zipfian_trace(g.num_nodes, args.requests, a=args.zipf_a, seed=1)
    rep = eng.serve(trace)
    print(f"served {rep.num_requests} requests in {rep.num_batches} "
          f"micro-batches: hit_rate={rep.hit_rate:.3f} "
          f"offchip={rep.cache.bytes_missed / 1e6:.2f}MB "
          f"p50={rep.p50_ms:.2f}ms p99={rep.p99_ms:.2f}ms "
          f"req/s={rep.req_per_s:.0f}")
    if not args.no_oracle:
        ok = rep.max_oracle_err < 1e-4
        print(f"oracle check (vs offline full-graph forward): "
              f"max_err={rep.max_oracle_err:.2e} -> "
              f"{'OK' if ok else 'MISMATCH'}")
        if not ok:
            raise SystemExit(1)
    return rep


def main(argv=None):
    ap = argparse.ArgumentParser()
    # LM path
    ap.add_argument("--arch", default="granite-8b")
    ap.add_argument("--tokens", type=int, default=16)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=16,
                    help="prompt length (also the decode cache offset)")
    # graph path
    ap.add_argument("--graph", default=None,
                    help="serve a GNN/recsys session over this dataset "
                         "(cora | citeseer-s | reddit) instead of the LM")
    ap.add_argument("--model", default="gcn",
                    help="registered serve session: gcn | sage_gin | wide_deep")
    ap.add_argument("--requests", type=int, default=200)
    ap.add_argument("--zipf-a", type=float, default=1.1)
    ap.add_argument("--cache-kb", type=int, default=500)
    ap.add_argument("--line-size", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--max-wait-ms", type=float, default=1.0)
    ap.add_argument("--warm", default="reorder",
                    choices=["reorder", "index", "none"])
    ap.add_argument("--scale", type=float, default=0.02,
                    help="dataset scale for citeseer-s/reddit stand-ins")
    ap.add_argument("--no-oracle", action="store_true")
    obs.add_cli_flags(ap)
    ap.add_argument("--summary", action="store_true",
                    help="after the run, print the repro.obs.summary "
                         "one-pager for --metrics-out / --trace files "
                         "(per-layer cache hit rates, queue-depth "
                         "high-watermark, latency percentiles)")
    args = ap.parse_args(argv)
    if args.summary and not (args.metrics_out or args.trace):
        ap.error("--summary needs --metrics-out and/or --trace")
    enable_compile_cache()
    try:
        with obs.observed_run(args.metrics_out, args.trace), \
                float32_matmuls():
            if args.graph is not None:
                return serve_graph(args)
            serve_lm(args)
    finally:
        if args.summary:
            from ..obs import summary as _summary
            _summary.main([f for f in (args.metrics_out, args.trace) if f])


if __name__ == "__main__":
    main()
