"""Serving demo: continuous-batching engine over the slot-pooled caches.

Submits a stream of staggered requests to ``repro.serve.ServeEngine``,
which admits each one with the real batched cache-writing prefill
(``model.prefill_with_cache`` via ``make_slot_prefill_step`` — one
projection for the whole prompt, not a token-by-token loop) and decodes
all live slots in a single fixed-shape jit call per tick. Works for
every registered causal arch family (attention KV caches, MLA latent
caches, SSM/xLSTM recurrent states).

    PYTHONPATH=src python examples/serve_lm.py --arch xlstm --tokens 32
"""

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.models import build_model
from repro.runtime.compile_cache import enable_compile_cache
from repro.serve import ServeEngine, Scheduler


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--tokens", type=int, default=24)
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="split long prompts into chunks this size "
                         "(bounds how long one admission stalls decoding)")
    ap.add_argument("--paged", action="store_true",
                    help="page the KV cache into a block arena with "
                         "admit-by-budget (DESIGN.md §11); greedy tokens "
                         "are byte-identical to the contiguous pool")
    ap.add_argument("--block-size", type=int, default=16,
                    help="paged mode: cache rows per block")
    ap.add_argument("--speculative", action="store_true",
                    help="attach a draft model for draft-then-verify "
                         "decoding (DESIGN.md §12); greedy tokens are "
                         "byte-identical, throughput is the only change")
    ap.add_argument("--draft", type=str, default=None, metavar="CFG",
                    help="draft arch (default: the target arch with "
                         "freshly initialized params — a deliberately "
                         "weak draft; watch the controller back off)")
    ap.add_argument("--gamma-max", type=int, default=4,
                    help="speculation: max draft tokens per round")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch).reduced()
    model = build_model(cfg)
    rng = jax.random.PRNGKey(0)
    params = model.init(rng)

    draft_model = draft_params = None
    if args.speculative:
        draft_cfg = get_config(args.draft).reduced() if args.draft else cfg
        if draft_cfg.vocab_size != cfg.vocab_size:
            raise SystemExit("--draft must share the target's vocabulary")
        draft_model = build_model(draft_cfg)
        draft_params = draft_model.init(jax.random.PRNGKey(1))

    max_len = args.prompt_len + args.tokens + 1
    engine = ServeEngine(
        model, params, n_slots=args.slots, max_len=max_len,
        scheduler=Scheduler(args.slots, prefill_chunk=args.prefill_chunk),
        block_size=args.block_size if args.paged else None,
        draft_model=draft_model, draft_params=draft_params,
        gamma_max=args.gamma_max,
    )

    host_rng = np.random.default_rng(0)
    for i in range(args.requests):
        plen = int(host_rng.integers(max(args.prompt_len // 2, 1), args.prompt_len + 1))
        prompt = host_rng.integers(0, cfg.vocab_size, size=plen).astype(np.int32)
        ntok = int(host_rng.integers(max(args.tokens // 2, 1), args.tokens + 1))
        engine.submit(prompt, ntok, arrival=i * 1e-3)

    t0 = time.perf_counter()
    results = engine.run()
    wall = time.perf_counter() - t0

    s = engine.stats
    mode = f"paged(block={args.block_size})" if args.paged else "contiguous"
    print(f"arch={cfg.name} slots={args.slots} requests={args.requests} "
          f"max_len={max_len} kv={mode}")
    if engine.pool.paged:
        mgr = engine.pool.manager
        print(f"kv arena: {mgr.used_high_water}/{mgr.num_blocks} blocks "
              f"high-water ({engine.pool.kv_bytes_high_water()} B vs "
              f"{engine.pool.kv_bytes_contiguous()} B contiguous)")
    print(f"prefill: {s.prefill_calls} calls / {s.prefill_tokens} tokens; "
          f"decode: {s.decode_ticks} ticks")
    if engine.speculative:
        print(f"speculation: {s.spec_rounds} rounds, {s.draft_ticks} draft "
              f"ticks, {s.spec_accepted} draft tokens accepted "
              f"(p_ewma={engine.spec.p:.3f}, accept hist "
              f"{engine.spec.hist.tolist()})")
    print(f"generated {s.generated_tokens} tokens in {wall:.2f}s wall "
          f"({s.generated_tokens / max(wall, 1e-9):.1f} tok/s on CPU) — "
          f"{s.tokens_per_vsec:.1f} tok/s virtual")
    for rid in sorted(results)[:2]:
        r = results[rid]
        print(f"  req{rid}: prompt={r.prompt_len} new={len(r.tokens)} "
              f"latency={r.latency:.4f}v  {r.tokens[:12]} ...")


if __name__ == "__main__":
    main()
