"""Serving launcher: prefill + batched decode with a KV cache.

``python -m repro.launch.serve --arch qwen3-0.6b --smoke --tokens 32``
runs prompt prefill then autoregressive decode, reporting tokens/s; the
recsys path scores batched requests (serve_p99 shape).

Mining-as-a-service: ``python -m repro.launch.serve --mine --graph
rmat:10 --queries tc,diamond,3-mc`` answers each query on the resident
graph, timing the first (cold) response and a warm repeat.  ``--plan
estimate`` (default for this mode) kills the first-query penalty: the
sampled estimator plans capacities in one small probe instead of the
per-level inspection pass, and ``--plan cache`` additionally seeds new
graphs from the profile-nearest cached plan (plan transfer).
"""
from __future__ import annotations

import argparse
import contextlib
import time

import jax
import jax.numpy as jnp

from repro.configs.registry import get_arch
from repro.launch.compile_cache import configure_compile_cache
from repro.obs import metrics, report, trace


def serve_lm(arch, smoke: bool, batch: int, prompt_len: int,
             gen_tokens: int, seed: int):
    from repro.models import transformer as T
    cfg = arch.smoke if smoke else arch.config
    key = jax.random.PRNGKey(seed)
    params = T.init_params(cfg, key)
    prompt = jax.random.randint(key, (batch, prompt_len), 0, cfg.vocab)
    total = prompt_len + gen_tokens
    logits, cache = jax.jit(
        lambda p, t: T.prefill(cfg, p, t, cache_len=total))(params, prompt)
    decode = jax.jit(lambda p, c, t, pos: T.decode_step(cfg, p, c, t, pos))
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    out = [tok]
    t0 = time.time()
    for i in range(gen_tokens - 1):
        logits, cache = decode(params, cache, tok,
                               jnp.int32(prompt_len + i))
        tok = jnp.argmax(logits[:, 0], -1)[:, None].astype(jnp.int32)
        out.append(tok)
    jax.block_until_ready(tok)
    dt = time.time() - t0
    toks = batch * (gen_tokens - 1)
    print(f"[serve] {arch.arch_id}: batch {batch}, prompt {prompt_len}, "
          f"decoded {toks} tokens in {dt:.2f}s "
          f"({toks / max(dt, 1e-9):.1f} tok/s)")
    return jnp.concatenate(out, axis=1)


def serve_recsys(arch, smoke: bool, batch: int, seed: int):
    from repro.data import pipeline as data_pipe
    from repro.models.recsys import dien as DN
    cfg = arch.smoke if smoke else arch.config
    params = DN.init_params(cfg, jax.random.PRNGKey(seed))
    fwd = jax.jit(lambda p, b: DN.forward(cfg, p, b))
    b = data_pipe.recsys_batch(seed, 0, batch, cfg.seq_len, cfg.n_items,
                               cfg.n_cats)
    t0 = time.time()
    scores = jax.block_until_ready(fwd(params, b))
    print(f"[serve] dien: scored {batch} requests in "
          f"{time.time()-t0:.3f}s")
    return scores


def query_app(query: str, minsup: int, stats):
    """The mining app a served query names: a built-in app (tc, k-cf,
    k-mc, k-fsm), else a library pattern compiled with a matching order
    picked by the resident graph's statistics."""
    from repro.core import Pattern, pattern_app
    from repro.launch.mine import make_app
    try:
        return make_app(query, minsup)
    except SystemExit:
        return pattern_app(Pattern.named(query), stats=stats)


def serve_mine(args):
    """Answer mining queries on a resident graph; returns per-query dicts.

    Each query runs once cold (plan + compile) and ``--query-repeats``
    warm repeats; latencies feed the ``serve.first_ms`` /
    ``serve.warm_ms`` histograms so the summary can report p50/p99 over
    the whole query stream, and each response carries the executor's
    plan provenance (``plan_reports()``).
    """
    from repro.core import Miner, graph_stats
    from repro.launch.mine import load_graph

    g = load_graph(args.graph, labels=args.labels)
    stats = graph_stats(g)
    print(f"[serve] mining graph {args.graph}: {g.n_vertices} vertices, "
          f"{g.n_edges // 2} edges, plan={args.plan}")
    results = []
    first_h = metrics.histogram("serve.first_ms")
    warm_h = metrics.histogram("serve.warm_ms")
    for query in [q.strip() for q in args.queries.split(",") if q.strip()]:
        app = query_app(query, args.minsup, stats)
        miner = Miner(g, app)
        with trace.span("serve.query", cat="serve", query=query):
            t0 = time.time()
            r = miner.run(plan_source=args.plan,
                          plan_cache=args.plan_cache,
                          safety_factor=args.safety_factor)
            cold_ms = (time.time() - t0) * 1e3
            first_h.observe(cold_ms)
            warm_ms = []
            for _ in range(max(args.query_repeats, 1)):
                t0 = time.time()
                miner.run(plan_source=args.plan,
                          plan_cache=args.plan_cache,
                          safety_factor=args.safety_factor)
                w = (time.time() - t0) * 1e3
                warm_ms.append(w)
                warm_h.observe(w)
        rep = miner.plan_reports()
        source = rep[0]["source"] if rep else "?"
        replans = sum(x["replans"] for x in rep)
        print(f"[serve] query {query!r}: count={r.count} "
              f"first={cold_ms:.0f}ms "
              f"warm={min(warm_ms):.1f}ms x{len(warm_ms)} "
              f"plan={source} replans={replans}")
        results.append({"query": query, "result": r,
                        "first_ms": cold_ms, "warm_ms": warm_ms,
                        "plan_reports": rep})
    print("[serve] " + report.latency_summary("first", first_h))
    print("[serve] " + report.latency_summary("warm", warm_h))
    return results


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None,
                    help="model arch to serve (required unless --mine)")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--tokens", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mine", action="store_true",
                    help="serve mining queries on a resident graph "
                         "instead of a model")
    ap.add_argument("--graph", default="rmat:10",
                    help="mining mode: resident graph spec")
    ap.add_argument("--queries", default="tc",
                    help="mining mode: comma-separated app or pattern "
                         "names (tc, 3-mc, 4-cf, k-fsm, diamond, ...)")
    ap.add_argument("--plan", default="estimate",
                    choices=("inspect", "estimate", "cache"),
                    help="mining mode: cold-query planning strategy")
    ap.add_argument("--plan-cache", default=None, metavar="DIR",
                    help="mining mode: persistent plan cache (enables "
                         "plan transfer across graphs with --plan cache)")
    ap.add_argument("--safety-factor", type=float, default=2.0)
    ap.add_argument("--minsup", type=int, default=100)
    ap.add_argument("--labels", type=int, default=None)
    ap.add_argument("--query-repeats", type=int, default=1,
                    help="mining mode: warm repeats per query (feeds the "
                         "serve.warm_ms latency histogram)")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="record host spans + plan events; write Chrome "
                         "trace-event JSON (open in ui.perfetto.dev)")
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="mining mode: run a JAX profiler session around "
                         "the queries, written under DIR, with the "
                         "program's spans on the device trace's clock")
    ap.add_argument("--metrics", nargs="?", const="-", default=None,
                    metavar="OUT",
                    help="dump the metrics registry after serving "
                         "('-'/no arg = text to stdout, *.json = JSON "
                         "snapshot) — the /metrics endpoint shape")
    return ap


def main(argv=None):
    args = build_parser().parse_args(argv)
    configure_compile_cache()
    if args.trace or args.profile:
        trace.enable(annotate=(jax.profiler.TraceAnnotation
                               if args.profile else None))
    if args.mine:
        with (jax.profiler.trace(args.profile) if args.profile
              else contextlib.nullcontext()):
            serve_mine(args)
        if args.trace:
            print(f"[serve] trace: {trace.save(args.trace)}")
        if args.metrics is not None:
            out = metrics.dump(args.metrics)
            print("[serve] metrics:" + ("\n" + out if args.metrics == "-"
                                        else " " + out))
        return
    if args.arch is None:
        raise SystemExit("--arch is required (or pass --mine)")
    arch = get_arch(args.arch)
    if arch.family == "lm":
        serve_lm(arch, args.smoke, args.batch, args.prompt_len,
                 args.tokens, args.seed)
    elif arch.family == "recsys":
        serve_recsys(arch, args.smoke, args.batch, args.seed)
    else:
        raise SystemExit("serving applies to lm/recsys archs")


if __name__ == "__main__":
    main()
