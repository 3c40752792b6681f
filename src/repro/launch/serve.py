"""Serving launcher: LM decode engine or the multi-tenant SpGEMM service.

LM mode (batched greedy decoding with the ServeEngine):

    PYTHONPATH=src python -m repro.launch.serve --arch granite-3-2b --smoke \
        --requests 4 --new-tokens 8

SpGEMM mode (pattern-coalescing micro-batcher over synthetic traffic):

    PYTHONPATH=src python -m repro.launch.serve --spgemm \
        --requests 64 --tenants 4 --patterns 6 --max-batch 8
"""
from __future__ import annotations

import argparse

import numpy as np


def run_lm(args) -> None:
    """Drive the fixed-slot LM ServeEngine over random prompts."""
    import jax

    from repro.configs import get_config, smoke_config
    from repro.models.transformer import init_transformer
    from repro.serve import Request, ServeEngine

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    params, _ = init_transformer(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, batch_slots=args.slots,
                      max_seq=args.max_seq)
    rng = np.random.default_rng(0)
    for i in range(args.requests):
        eng.submit(Request(prompt=rng.integers(0, cfg.vocab, 4 + i % 3),
                           max_new_tokens=args.new_tokens))
    done = eng.run()
    for i, r in enumerate(done):
        print(f"[serve] req{i}: prompt={list(r.prompt)} -> {r.out_tokens}")


def spgemm_trace(n: int = 64, density: float = 0.1, patterns: int = 6,
                 requests: int = 64, tenants: int = 4, zipf: float = 1.2,
                 seed: int = 0):
    """Synthetic multi-tenant SpGEMM traffic: ``[(tenant, a, b), ...]``.

    ``patterns`` random ``n × n`` sparsity masks with Zipf-distributed
    popularity (a few hot patterns dominate, which is what makes
    coalescing pay); each request carries fresh values on its pattern's A
    and the pattern's shared B, from tenants taken round-robin.
    """
    from repro.sparse.formats import csr_from_dense

    rng = np.random.default_rng(seed)
    masks = [rng.random((n, n)) < density for _ in range(patterns)]
    b_side = [csr_from_dense((m * rng.standard_normal((n, n)))
                             .astype(np.float32)) for m in masks]
    ranks = np.arange(1, patterns + 1, dtype=np.float64)
    popularity = ranks ** -zipf
    popularity /= popularity.sum()
    trace = []
    for i in range(requests):
        pid = int(rng.choice(patterns, p=popularity))
        vals = rng.standard_normal((n, n)).astype(np.float32)
        a = csr_from_dense((masks[pid] * vals).astype(np.float32))
        trace.append((f"tenant{i % tenants}", a, b_side[pid]))
    return trace


def run_spgemm(args) -> None:
    """Drive the SpGEMMService over Zipf-popular synthetic patterns."""
    from repro.serve import SpGEMMService

    svc = SpGEMMService(max_batch=args.max_batch, max_wait=args.max_wait,
                        max_queue=args.max_queue)
    for tenant, a, b in spgemm_trace(
            n=args.n, density=args.density, patterns=args.patterns,
            requests=args.requests, tenants=args.tenants, zipf=args.zipf,
            seed=args.seed):
        svc.submit(tenant, a, b)
    svc.flush()
    s = svc.stats()
    print(f"[spgemm-serve] {s['requests_completed']} requests in "
          f"{s['dispatches']} dispatches "
          f"(coalescing ratio {s['coalescing_ratio']:.2f}, "
          f"{s['batched_dispatches']} batched / "
          f"{s['singleton_dispatches']} singleton)")
    print(f"[spgemm-serve] latency p50={s['latency_p50_ms']:.2f}ms "
          f"p99={s['latency_p99_ms']:.2f}ms shed={s['requests_shed']}")
    for tid, ten in s["tenants"].items():
        print(f"[spgemm-serve]   {tid}: {ten['completed']} done, "
              f"plan hit rate {ten['plan_hit_rate']:.2f} "
              f"({ten['plan_entries']} plans cached)")


def main():
    """Parse args and dispatch to the LM or SpGEMM serving mode."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--spgemm", action="store_true",
                    help="serve SpGEMM requests instead of LM decoding")
    ap.add_argument("--arch", help="LM mode: architecture name")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=64)
    # SpGEMM-service knobs
    ap.add_argument("--tenants", type=int, default=4)
    ap.add_argument("--patterns", type=int, default=6)
    ap.add_argument("--n", type=int, default=64)
    ap.add_argument("--density", type=float, default=0.1)
    ap.add_argument("--zipf", type=float, default=1.2)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--max-wait", type=float, default=0.01)
    ap.add_argument("--max-queue", type=int, default=1024)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.spgemm:
        run_spgemm(args)
    else:
        if not args.arch:
            ap.error("--arch is required unless --spgemm is given")
        run_lm(args)


if __name__ == "__main__":
    main()
