"""Serving launcher: LM decode loop or the implicit-diff solve service.

LM decode (batched prefill + decode with a KV cache)::

    PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-4b --smoke \
        --batch 4 --prompt-len 16 --gen 16

Solve service (continuous-batching linear-solve front end; drives two
traffic waves — the second replays the first, so the warm-start cache
hit rate and scheduler metrics are exercised end to end; each wave is
submitted whole and flushed, so its buckets fill to ``--max-batch``)::

    PYTHONPATH=src python -m repro.launch.serve --solve-service \
        --requests 64 --dim 32 --max-batch 64

The LM path implements the production serve loop shape: one prefill pass
fills the cache, then decode steps run one token/step for the whole batch
(greedy).  The solve-service path is documented in ``docs/serving.md``.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro import configs
from repro.launch.compile_cache import enable_compile_cache
from repro.models import decode_step, init_decode_state, init_params


def drive_service(problems, *, max_batch: int = 64,
                  cache_capacity: int = 256):
    """Serve SPD ``problems`` ``[(A, b), ...]`` in two waves; the
    solve-service path of this launcher.

    Each wave submits every problem to one ``SolveService`` (warm-start
    cache on) and flushes, so the warm wave replays the cold one and hits
    the cache.  Returns ``(svc, stats)``: one dict per wave with its ``wave``
    name, ``results`` (``ServiceResult`` per problem, in order),
    ``seconds`` from first submit to last result, and the service's
    ``metrics_summary()`` after the wave.  Telemetry follows the caller's
    ``repro.observability.observe`` switch.
    """
    from repro.runtime.solve_service import SolveService, WarmStartCache

    svc = SolveService(max_batch=max_batch,
                       cache=WarmStartCache(capacity=cache_capacity))
    stats = []
    for wave in ("cold", "warm"):
        t0 = time.perf_counter()
        futs = [svc.submit(A, b, positive_definite=True)
                for A, b in problems]
        svc.flush()
        results = [f.result() for f in futs]
        stats.append(dict(svc.metrics_summary(), wave=wave, results=results,
                          seconds=time.perf_counter() - t0))
    return svc, stats


def serve_solves(args) -> None:
    """Drive the solve service with synthetic SPD traffic; print metrics.

    Observability is enabled for the whole run (``--trace PATH`` also
    streams a JSONL span/event trace for
    ``python -m repro.observability.report``); the scheduler metrics come
    from the service's ``MetricsRegistry`` snapshot and the full
    Prometheus text exposition is printed once at exit.
    """
    import numpy as np

    from repro import observability as obs

    rng = np.random.default_rng(args.seed)
    n, d = args.requests, args.dim
    problems = []
    for _ in range(n):
        M = rng.standard_normal((d, d))
        problems.append((M @ M.T + d * np.eye(d), rng.standard_normal(d)))

    # enable BEFORE constructing the service: programs jitted while
    # disabled would stay uninstrumented until re-traced
    with obs.observe(enabled=True, trace_path=args.trace):
        svc, stats = drive_service(problems, max_batch=args.max_batch,
                                   cache_capacity=args.cache_capacity)
        for st in stats:
            results, dt = st["results"], st["seconds"]
            iters = [int(r.info.iterations) for r in results]
            print(f"[serve] {st['wave']}: {n} requests d={d} in "
                  f"{dt*1e3:.1f}ms ({n / dt:.0f} req/s) "
                  f"iters(median)={int(np.median(iters))} "
                  f"warm_started={sum(r.warm_start for r in results)}")
        snap = svc.metrics_snapshot()

        def _val(name, default=0.0):
            values = snap.get(name, {}).get("values", {})
            v = values.get("", default)
            return v["sum"] if isinstance(v, dict) else v

        dispatches = _val("repro_service_dispatches_total")
        print(f"[serve] dispatches={int(dispatches)} "
              f"compiled={int(_val('repro_service_compiled_programs'))} "
              f"occupancy="
              f"{_val('repro_service_occupancy_sum') / max(dispatches, 1):.2f} "
              f"hit_rate={svc.hit_rate:.2f} "
              f"cache_size={len(svc.cache) if svc.cache else 0}")
        print("[serve] prometheus exposition:")
        print(svc.registry.to_prometheus(), end="")
        tracer = obs.current_tracer()
        if tracer is not None:
            tracer.flush()
            n_spans = sum(1 for r in tracer.records()
                          if r.get("type") == "span")
            print(f"[serve] trace: {tracer.path} ({n_spans} spans)")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=configs.names(),
                    help="LM decode mode (required unless --solve-service)")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--solve-service", action="store_true",
                    help="serve the implicit-diff solve service instead of "
                         "LM decode")
    ap.add_argument("--requests", type=int, default=64,
                    help="solve-service: concurrent requests per wave")
    ap.add_argument("--dim", type=int, default=32,
                    help="solve-service: instance dimension d")
    ap.add_argument("--max-batch", type=int, default=64,
                    help="solve-service: bucket capacity ceiling")
    ap.add_argument("--cache-capacity", type=int, default=256,
                    help="solve-service: warm-start cache capacity")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="solve-service: write a JSONL span/event trace "
                         "(summarize with repro.observability.report)")
    args = ap.parse_args()
    enable_compile_cache()

    if args.solve_service:
        serve_solves(args)
        return
    if args.arch is None:
        ap.error("--arch is required unless --solve-service is given")

    cfg = configs.get(args.arch, smoke=args.smoke)
    if not cfg.has_decoder:
        raise SystemExit(f"{cfg.name} is encoder-only; nothing to decode")
    key = jax.random.PRNGKey(args.seed)
    params = init_params(key, cfg)
    B, P, G = args.batch, args.prompt_len, args.gen

    if cfg.embedding_frontend == "stub_embeddings":
        prompts = jax.random.normal(key, (B, P, cfg.d_model))
        def embed_tok(tok):
            return jax.random.normal(jax.random.fold_in(key, 1),
                                     (B, 1, cfg.d_model))
    else:
        prompts = jax.random.randint(key, (B, P), 0, cfg.vocab_size)
        embed_tok = None

    state = init_decode_state(cfg, B, P + G)
    step = jax.jit(lambda p, s, t: decode_step(p, cfg, s, t))

    # prefill: feed the prompt through decode steps (cache-filling).  A
    # chunked prefill (full forward + cache scatter) is the optimized path
    # exercised by the prefill_32k dry-run cells.
    t0 = time.perf_counter()
    logits = None
    for i in range(P):
        tok = prompts[:, i:i + 1]
        logits, state = step(params, state, tok)
    jax.block_until_ready(logits)
    t_prefill = time.perf_counter() - t0

    generated = []
    t0 = time.perf_counter()
    tok = jnp.argmax(logits[:, -1], axis=-1)[:, None]
    for _ in range(G):
        if embed_tok is not None:
            inp = embed_tok(tok)
        else:
            inp = tok
        logits, state = step(params, state, inp)
        tok = jnp.argmax(logits[:, -1], axis=-1)[:, None]
        generated.append(tok)
    jax.block_until_ready(logits)
    t_decode = time.perf_counter() - t0

    out = jnp.concatenate(generated, axis=1)
    print(f"[serve] arch={cfg.name} batch={B} prompt={P} gen={G}")
    print(f"[serve] prefill={t_prefill*1e3:.1f}ms "
          f"decode={t_decode*1e3:.1f}ms "
          f"({B * G / max(t_decode, 1e-9):.1f} tok/s)")
    print(f"[serve] sample tokens: {out[0, :8].tolist()}")


if __name__ == "__main__":
    main()
