"""End-to-end training driver.

Runs for real on whatever devices exist (CPU at reduced scale; the
production mesh on TPU). Examples:

  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b \
      --variant reduced --steps 200 --batch 16 --seq 128
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import jax
import numpy as np

from repro.configs import ARCHS, get_arch
from repro.data.pipeline import MarkovLM, batches_for
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import build
from repro.training.checkpoints import save_train_state
from repro.training.optimizer import AdamW, cosine_schedule
from repro.training.train_loop import init_train_state, make_train_step


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), default="llama3.2-1b")
    ap.add_argument("--variant", default="reduced")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--save", default="")
    ap.add_argument("--metrics", default="",
                    help="JSONL metrics path (machine-readable run log)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_arch(args.arch, variant=args.variant)
    model = build(cfg)
    opt = AdamW(lr=cosine_schedule(args.lr, args.warmup, args.steps))
    data = batches_for(cfg, args.batch, args.seq, seed=args.seed)

    state = init_train_state(model, opt, jax.random.PRNGKey(args.seed))
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree.leaves(state["params"]))
    floor = MarkovLM(cfg.vocab).entropy_bound()
    print(f"arch={cfg.name} params={n_params/1e6:.1f}M "
          f"devices={jax.device_count()} loss_floor~{floor:.3f}")

    from repro.training.metrics import MetricsLogger
    mlog = MetricsLogger(args.metrics or None, run_name=cfg.name)
    step_fn = jax.jit(make_train_step(model, opt,
                                      microbatch=args.microbatch))
    t0 = time.perf_counter()
    history = []
    for i in range(args.steps):
        batch = next(data)
        state, metrics = step_fn(state, batch)
        if i % args.log_every == 0 or i == args.steps - 1:
            m = {k: float(v) for k, v in metrics.items()}
            wall = time.perf_counter() - t0
            tok_s = (i + 1) * args.batch * args.seq / wall
            print(f"step {i:5d} loss={m['loss']:.4f} "
                  f"grad_norm={m['grad_norm']:.3f} lr={m['lr']:.2e} "
                  f"tok/s={tok_s:,.0f}")
            history.append({"step": i, **m, "wall_s": wall})
            mlog.log("train", step=i, tok_s=tok_s, **m)
    mlog.close()
    if args.save:
        save_train_state(args.save, args.steps, state["params"],
                         state["opt"])
        with open(Path(args.save) / "history.json", "w") as f:
            json.dump(history, f, indent=1)
        print(f"saved to {args.save}")
    return history


if __name__ == "__main__":
    main()
