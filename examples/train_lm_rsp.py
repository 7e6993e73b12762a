"""End-to-end LM training on an RSP token corpus with checkpoint/restart.

The corpus is partitioned into RSP blocks of sequences; the training loader
consumes block-level samples, so every global batch is a random sample of
the corpus with no run-time shuffle and an O(1)-byte data-pipeline
checkpoint.  Mid-run the script simulates a preemption and restarts from
the latest checkpoint.

Presets:
    cpu-small (default): ~7M-param llama-style model, runs in minutes on CPU
    100m: ~115M params, seq 1024 -- the "train ~100M for a few hundred
          steps" driver for real hardware (works on CPU too, just slowly)

    PYTHONPATH=src python examples/train_lm_rsp.py --steps 60
"""

import argparse
import dataclasses
import tempfile

import jax.numpy as jnp

from repro import rsp
from repro.configs import ARCHS
from repro.data.synthetic import make_token_corpus
from repro.optim import AdamWConfig
from repro.runtime import use_compile_cache
from repro.train import TrainConfig, Trainer

PRESETS = {
    "cpu-small": dict(num_layers=4, d_model=256, num_heads=8, num_kv_heads=4,
                      d_ff=1024, vocab_size=2048, seq=128, batch=8),
    "100m": dict(num_layers=12, d_model=768, num_heads=12, num_kv_heads=4,
                 d_ff=3072, vocab_size=32000, seq=1024, batch=32),
}


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", choices=sorted(PRESETS), default="cpu-small")
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--preempt-at", type=int, default=None,
                    help="simulate preemption after N steps, then restart")
    ap.add_argument("--arch", default="llama3.2-1b", choices=sorted(ARCHS))
    args = ap.parse_args()

    p = PRESETS[args.preset]
    cfg = dataclasses.replace(
        ARCHS[args.arch],
        num_layers=p["num_layers"], d_model=p["d_model"], num_heads=p["num_heads"],
        num_kv_heads=p["num_kv_heads"], d_ff=p["d_ff"], vocab_size=p["vocab_size"],
        head_dim=0,
    )
    seq, batch = p["seq"], p["batch"]

    # --- corpus -> RSP blocks of sequences ---------------------------------
    n_seqs, K = 512, 16   # N/(P*K) must be integral: 512/(16*16) = 2
    corpus = make_token_corpus(n_seqs, seq + 1, vocab_size=cfg.vocab_size, seed=0, drift=True)
    # int token data: backend="auto" routes to the numpy streaming path
    ds = rsp.partition(corpus, blocks=K, seed=1, summaries=False)
    print(f"corpus: {n_seqs} sequences x {seq + 1} tokens -> {K} RSP blocks "
          f"(backend={ds.backend!r})")

    ckpt_dir = tempfile.mkdtemp(prefix="rsp_lm_ckpt_")
    tc = TrainConfig(total_steps=args.steps, warmup_steps=max(args.steps // 10, 1),
                     checkpoint_every=max(args.steps // 3, 1), log_every=5, seed=0)

    def make_trainer():
        return Trainer(
            cfg, AdamWConfig(lr=3e-3), tc,
            ds.loader(batch_size=batch, seed=5),
            ckpt_dir,
            batch_transform=lambda b: {"tokens": jnp.asarray(b, jnp.int32)},
        )

    preempt = args.preempt_at or args.steps // 2
    print(f"training {args.steps} steps; simulating preemption at {preempt}")
    t1 = make_trainer()
    t1.run(stop_after_steps=preempt)
    print(f"-- preempted; checkpoint saved; restarting fresh --")
    t2 = make_trainer()
    t2.run()
    for h in t1.history + t2.history:
        print(f"  step {h['step']:4d} loss {h['loss']:.4f} "
              f"({h['sec_per_step']:.2f}s/step)")
    first, last = t1.history[0]["loss"], t2.history[-1]["loss"]
    print(f"loss {first:.4f} -> {last:.4f} "
          f"({'OK' if last < first else 'NOT DECREASING'}); "
          f"restart resumed exactly from the checkpointed sampler state")


if __name__ == "__main__":
    main()
