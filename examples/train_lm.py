"""End-to-end driver: train an LM with the adaptive-(k, beta) controller.

The full production path: synthetic token pipeline -> per-stage
beta-scaled batches -> masked fastest-k aggregation (simulated worker
delays) -> AdamW -> stationarity-diagnostic stage advancement -> async
checkpoints. Identical code path to a TPU run; on CPU use the default
tiny preset (visible learning in ~2 minutes).

    PYTHONPATH=src python examples/train_lm.py                 # tiny, CPU
    PYTHONPATH=src python examples/train_lm.py --preset smollm # ~135M (TPU)
    PYTHONPATH=src python examples/train_lm.py --resume        # restart test
"""

import argparse
import dataclasses

from repro.configs import get_config
from repro.core import DiagnosticConfig, SimplifiedDelayModel, StrategyConfig
from repro.data import StagedBatcher, TokenStream
from repro.models import build_model
from repro.optim.optimizers import get_optimizer
from repro.runtime.compile_cache import enable_compile_cache
from repro.runtime.train_loop import TrainLoopConfig, train


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", choices=["tiny", "smollm"], default="tiny")
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--n-workers", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=32)
    ap.add_argument("--checkpoint-dir", type=str, default=None)
    ap.add_argument("--fail-worker-at", type=int, default=None,
                    help="inject a worker failure at this step")
    args = ap.parse_args()
    enable_compile_cache()

    if args.preset == "smollm":
        cfg = get_config("smollm-135m")
        cfg = dataclasses.replace(cfg, max_seq_len=args.seq_len, remat="none",
                                  dtype="float32", scan_layers=True)
    else:
        cfg = get_config("smollm-135m").reduced(
            n_layers=4, d_model=128, vocab_size=512, max_seq_len=args.seq_len
        )
    model = build_model(cfg)
    optimizer = get_optimizer("adamw", weight_decay=0.01)

    n = args.n_workers
    strategy = StrategyConfig(
        "adaptive_kbeta",
        n=n,
        s=args.global_batch // n,
        k_max=n // 2,
        beta_grid=(0.25, 0.5, 0.75, 1.0),
        diagnostic=DiagnosticConfig(kind="loss", rel_tol=0.02, min_iters=10,
                                    consecutive=3),
    )
    delay_model = SimplifiedDelayModel(lambda_y=1.0, x=0.05)
    batcher = StagedBatcher(
        TokenStream(cfg.vocab_size, seed=0),
        n_workers=n,
        global_batch=args.global_batch,
        seq_len=args.seq_len,
    )
    loop_cfg = TrainLoopConfig(
        total_steps=args.steps,
        lr=3e-4,
        checkpoint_dir=args.checkpoint_dir,
        checkpoint_every=100,
        log_every=20,
        fail_worker_at=args.fail_worker_at,
    )
    out = train(model, optimizer, strategy, delay_model, batcher, loop_cfg)
    hist = out["history"]
    print(f"\nfinal loss {hist[-1]['loss']:.4f} (start {hist[0]['loss']:.4f})")
    print(f"stage path: {[(h['k'], h['beta']) for h in hist if 'switched_to' in h]}")
    print(f"compiled step shapes (one per beta): {out['compiled_shapes']}")
    print(f"simulated wall-clock: {out['sim_time']:.1f}")


if __name__ == "__main__":
    main()
