"""Serving launcher: reduced-scale prefill + decode with optional kNN-LM
retrieval through a Pyramid datastore served by the distributed engine
(lookups go through the futures-based ``PyramidClient`` session).

Observability: ``--trace-out trace.json`` records the whole run —
prefill, every decode step, and (with ``--retrieval``) the engine-side
route/dispatch/batch/merge spans under them — as Chrome ``trace_event``
JSON loadable in Perfetto / ``chrome://tracing``. ``--metrics-port``
serves the run's registry at ``/metrics`` (Prometheus text) and
``/stats`` while it lasts.

For real launches, source the host-tuning environment first (tcmalloc
preload when available + XLA host-platform flags; measured effect in
API.md "Serving host environment"):

    source scripts/serve_env.sh
    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b --tokens 16
"""
from __future__ import annotations

import argparse
import contextlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.compile_cache import enable_compile_cache
from repro.common.config import PyramidConfig
from repro.common.registry import get_arch, list_archs
from repro.models.transformer import grow_cache, init_params
from repro.obs import MetricsRegistry, StatsServer, Tracer, get_logger
from repro.serving.decode import decode_step, prefill_step
from repro.serving.retrieval import (build_datastore, hidden_states,
                                     interpolate, knn_probs,
                                     open_datastore_client)

log = get_logger(__name__)


def main(argv=None) -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b", choices=list_archs())
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--tokens", type=int, default=16)
    ap.add_argument("--retrieval", action="store_true",
                    help="kNN-LM interpolation via a Pyramid datastore")
    ap.add_argument("--quantize", action="store_true",
                    help="serve the retrieval datastore from the int8 "
                         "arena (asymmetric distances + exact float32 "
                         "rerank; ~4x smaller device vector payload)")
    ap.add_argument("--rerank-factor", type=int, default=4,
                    help="with --quantize: exact-rerank the top "
                         "rerank_factor * k quantized candidates")
    ap.add_argument("--tenant", default=None, metavar="NAME",
                    help="serve the retrieval datastore as this named "
                         "tenant through a TenantManager (admission-"
                         "controlled device-memory budget, LRU "
                         "eviction; see repro.serving.tenancy)")
    ap.add_argument("--tenant-budget-mb", type=float, default=256.0,
                    help="with --tenant: the manager's total device-"
                         "memory budget for tenant arenas, in MiB")
    ap.add_argument("--lam", type=float, default=0.3)
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Chrome trace_event JSON of the run "
                         "(validated; open in Perfetto)")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="serve /metrics (Prometheus) and /stats on "
                         "this port for the duration of the run "
                         "(0 = ephemeral)")
    args = ap.parse_args(argv)

    tracer = Tracer() if args.trace_out else None
    registry = MetricsRegistry()
    server = None
    if args.metrics_port is not None:
        server = StatsServer(registry, port=args.metrics_port).start()
        log.info("[serve] stats server on :%d (/metrics /stats)",
                 server.port)

    cfg = get_arch(args.arch).reduced()
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    if cfg.frontend:
        prompt = jnp.asarray(rng.normal(size=(
            args.batch, args.prompt_len, cfg.frontend_dim)).astype(
                np.float32))
    else:
        prompt = jnp.asarray(rng.integers(
            0, cfg.vocab_size, size=(args.batch, args.prompt_len)),
            jnp.int32)

    ds = None
    ds_client = None
    span = (tracer.span if tracer else
            (lambda *a, **kw: contextlib.nullcontext()))
    # the datastore client is a context manager owning its engine: the
    # with-block guarantees the executor threads come down on any exit
    # path (an abandoned engine can abort the interpreter mid-XLA-call)
    with contextlib.ExitStack() as stack:
        if args.retrieval:
            if cfg.frontend:
                raise SystemExit("--retrieval expects a token-input arch")
            corpus = rng.integers(0, cfg.vocab_size, size=(8, 64))
            pyr = PyramidConfig(metric="l2", num_shards=4, meta_size=32,
                                sample_size=400, branching_factor=2,
                                max_degree=12, max_degree_upper=6,
                                ef_construction=40, ef_search=60)
            with span("serve.build_datastore"):
                ds = build_datastore(params, cfg, [corpus], pyr)
                if args.tenant:
                    from repro.serving.tenancy import TenantManager
                    tm = stack.enter_context(TenantManager(
                        int(args.tenant_budget_mb * 2**20),
                        registry=registry))
                    tm.create(args.tenant, ds.index,
                              quantize=args.quantize,
                              rerank_factor=args.rerank_factor,
                              tracer=tracer)
                    ds_client = tm.client(args.tenant)
                    log.info("[serve] tenant %r admitted: %s",
                             args.tenant, tm.stats()["tenants"])
                    if server is not None:
                        server.add_stats_provider("tenancy", tm.stats)
                else:
                    ds_client = stack.enter_context(
                        open_datastore_client(
                            ds, quantize=args.quantize,
                            rerank_factor=args.rerank_factor,
                            registry=registry, tracer=tracer))
            stats = ds_client.stats()
            log.info(
                "[serve] datastore ready: %d entries, served by %d "
                "executors (quantized=%s, arena vector bytes=%d)",
                ds.values.shape[0], len(stats["executors"]),
                stats["quantized"], stats["arena_vector_bytes"])
            if server is not None:
                server.add_stats_provider("engine", ds_client.stats)

        t0 = time.time()
        with span("serve.prefill", batch=args.batch,
                  prompt_len=args.prompt_len):
            logits, cache = prefill_step(params, prompt, cfg=cfg)
            cache = grow_cache(cache, args.prompt_len + args.tokens,
                               window=cfg.sliding_window)
        log.info("[serve] prefill %s in %.2fs", tuple(prompt.shape),
                 time.time() - t0)

        tok = jnp.argmax(logits[:, -1:].astype(jnp.float32),
                         -1).astype(jnp.int32)
        if cfg.frontend:  # frontend archs decode over embedding stand-ins
            tok_emb = jnp.zeros((args.batch, 1, cfg.frontend_dim),
                                jnp.float32)
        out_tokens = [np.asarray(tok[:, 0])]
        t0 = time.time()
        for t in range(args.tokens - 1):
            with span("serve.decode_step", step=t):
                pos = jnp.full((args.batch,), args.prompt_len + t,
                               jnp.int32)
                inp = tok_emb if cfg.frontend else tok
                nxt, step_logits, cache = decode_step(params, cache, inp,
                                                      pos, cfg=cfg)
                if ds is not None:
                    # demo-grade retrieval key: context-free hidden
                    # state of the last token (the retrieval_decode
                    # example shows the full flow)
                    kp = knn_probs(ds, np.asarray(
                        hidden_states(params, cfg, tok),
                        np.float32)[:, -1], k=8,
                        vocab_size=cfg.vocab_size, client=ds_client)
                    mixed = interpolate(np.asarray(step_logits), kp,
                                        lam=args.lam)
                    nxt = jnp.asarray(mixed.argmax(-1), jnp.int32)
                tok = nxt[:, None]
                out_tokens.append(np.asarray(nxt))
        dt = time.time() - t0
    gen = np.stack(out_tokens, axis=1)
    log.info("[serve] decoded %d tokens/seq in %.2fs (%.1f tok/s)",
             args.tokens, dt, args.batch * args.tokens / dt)
    log.info("[serve] generated ids (row 0): %s", gen[0][:16])
    if tracer is not None:
        payload = tracer.write_chrome(args.trace_out)
        log.info("[serve] wrote %d trace events to %s",
                 len(payload["traceEvents"]), args.trace_out)
    if server is not None:
        server.stop()


if __name__ == "__main__":
    main()
