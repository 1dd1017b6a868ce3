"""End-to-end training driver.

Examples::

    # tiny CPU run (reduced config), fault-tolerant loop
    PYTHONPATH=src python -m repro.launch.train --arch granite-8b --reduced \\
        --steps 50 --batch 8 --seq 128

    # delayed gradient commit (paper's technique at training scale)
    PYTHONPATH=src python -m repro.launch.train --arch minicpm-2b --reduced \\
        --steps 50 --commit-delta 4 --n-pods 2
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_reduced
from repro.data.pipeline import SyntheticLM, make_encdec_batch, make_vlm_batch
from repro.dist.delayed_commit import (
    DelayedCommitConfig,
    init_delayed_state,
    make_delayed_commit_step,
)
from repro.ft.runner import FailureInjector, RunnerConfig, run_training
from repro.train.optimizer import AdamW, linear_warmup_cosine, wsd
from repro.train.train_step import init_train_state, make_train_step


def build_batch_fn(cfg, seq, batch, n_pods=0):
    data = SyntheticLM(vocab=cfg.vocab, seq_len=seq, global_batch=batch)

    def batch_fn(step):
        b = data.batch(step)
        if cfg.family == "vlm":
            b = make_vlm_batch(b, cfg.d_model)
        elif cfg.family == "encdec":
            b = make_encdec_batch(b, cfg.d_model, cfg.enc_seq)
        b = {k: jnp.asarray(v) for k, v in b.items()}
        if n_pods:
            b = jax.tree.map(
                lambda x: x.reshape((n_pods, x.shape[0] // n_pods) + x.shape[1:]), b
            )
        return b

    return batch_fn


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--schedule", default="cosine", choices=["cosine", "wsd"])
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--commit-delta", type=int, default=0,
                    help="δ for delayed gradient commit (0 = plain sync DP)")
    ap.add_argument("--n-pods", type=int, default=2)
    ap.add_argument("--compress", default="none", choices=["none", "int8", "topk"])
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--fail-at", type=int, nargs="*", default=[])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    sched = (
        wsd(args.lr, warmup=args.steps // 10, stable=int(args.steps * 0.7),
            decay=max(args.steps // 5, 1))
        if args.schedule == "wsd"
        else linear_warmup_cosine(args.lr, warmup=args.steps // 10, total=args.steps)
    )
    opt = AdamW(schedule=sched)
    key = jax.random.PRNGKey(args.seed)

    if args.commit_delta > 0:
        if args.batch % args.n_pods:
            ap.error(
                f"--batch {args.batch} must be divisible by --n-pods {args.n_pods}"
            )
        cc = DelayedCommitConfig(
            n_pods=args.n_pods, delta=args.commit_delta, compress=args.compress
        )
        state = init_delayed_state(cfg, opt, cc, key)
        step_fn = jax.jit(make_delayed_commit_step(cfg, opt, cc))
        batch_fn = build_batch_fn(cfg, args.seq, args.batch, n_pods=args.n_pods)
    else:
        state = init_train_state(cfg, opt, key)
        step_fn = jax.jit(make_train_step(cfg, opt, accum_steps=args.accum))
        batch_fn = build_batch_fn(cfg, args.seq, args.batch)

    def on_metrics(step, metrics, dt):
        loss = float(metrics.get("total_loss", metrics.get("loss")))
        print(f"step {step:5d}  loss {loss:8.4f}  {dt*1e3:7.1f} ms/step", flush=True)

    rcfg = RunnerConfig(
        total_steps=args.steps, ckpt_every=args.ckpt_every, ckpt_dir=args.ckpt_dir
    )
    injector = FailureInjector(args.fail_at)
    t0 = time.time()
    state, hist = run_training(
        state, step_fn, batch_fn, rcfg, injector=injector, on_metrics=on_metrics
    )
    print(
        f"done: {args.steps} steps in {time.time()-t0:.1f}s — "
        f"loss {hist['loss'][0]:.4f} → {hist['loss'][-1]:.4f}, "
        f"restarts={hist['restarts']} stragglers={hist['stragglers']} "
        f"ckpts={hist['ckpts']}"
    )
    return hist


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
