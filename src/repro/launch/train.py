"""FL training launcher.

    PYTHONPATH=src python -m repro.launch.train \
        --arch deepseek-7b --smoke --rounds 20 --clients 6 --algorithm auto

Uses the assigned architecture's (reduced, unless --full) config as the FL
model, a simulated heterogeneous fleet, and the paper's scheduler for the
per-round workload split. On real hardware, point the estimator at measured
device profiles instead of the simulator.
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from ..configs import get_config
from ..data import client_corpora, make_lm_examples
from ..fl import EnergyEstimator, FederatedServer, make_fleet, run_campaign
from ..models import init_params, loss_fn, param_count
from ..optim import sgd
from ..checkpoint import save_checkpoint
from .compile_cache import use_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="deepseek-7b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--rounds", type=int, default=10)
    ap.add_argument("--clients", type=int, default=6)
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--max-batches", type=int, default=8)
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument("--algorithm", default="auto")
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_config(args.arch, smoke=args.smoke)
    if cfg.family not in ("dense", "moe", "ssm", "hybrid"):
        raise SystemExit(f"{args.arch} ({cfg.family}) is not an LM; pick a decoder arch")
    params = init_params(cfg, jax.random.PRNGKey(args.seed))
    print(f"arch={cfg.arch} ({'smoke' if args.smoke else 'full'}): "
          f"{param_count(params) / 1e6:.2f}M params")

    rng = np.random.default_rng(args.seed)
    fleet = make_fleet(rng, args.clients, max_batches=args.max_batches)
    est = EnergyEstimator(fleet)
    est.calibrate(rng)
    corpora = client_corpora(rng, args.clients, args.seq * 120, cfg.vocab_size)
    examples = [make_lm_examples(c, args.seq) for c in corpora]

    server = FederatedServer(
        loss_fn=lambda p, b: loss_fn(p, cfg, {"tokens": b}),
        init_params=params,
        client_optimizer=sgd(args.lr),
        estimator=est,
        algorithm=args.algorithm,
    )
    T = sum(d.max_batches for d in fleet) // 2
    t0 = time.time()
    hist = run_campaign(
        server, examples, args.rounds, round_T=T, batch_size=args.batch, rng=rng,
        on_round=lambda r: print(
            f"round {r.round_index:3d} loss {r.mean_loss:.4f} "
            f"energy {r.energy_joules:7.1f} J x={list(r.assignments)}"
        ),
    )
    print(f"\nwall {time.time() - t0:.1f}s  {hist.summary()}")
    if args.checkpoint_dir:
        path = save_checkpoint(args.checkpoint_dir, args.rounds, server.params,
                               extra={"arch": cfg.arch, "algorithm": args.algorithm})
        print(f"checkpoint: {path}")


if __name__ == "__main__":
    main()
