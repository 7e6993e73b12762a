"""Batched serving with KV-cache decode, plus RSP-ensemble serving.

Trains k tiny LMs on disjoint RSP block samples (Algorithm 2 applied to
language models), then serves batched requests from (a) a single model and
(b) the logit-averaged ensemble (Sec. 9's combination function at decode
time).

    PYTHONPATH=src python examples/serve_decode.py
"""

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS
from repro.core import RSPSpec, two_stage_partition_np
from repro.core.sampler import BlockSampler
from repro.data.synthetic import make_token_corpus
from repro.models import api
from repro.models.common import init_params
from repro.optim import AdamWConfig
from repro.runtime import use_compile_cache
from repro.serve.engine import EnsembleServer, ServeConfig, Server
from repro.train import TrainConfig, init_state, make_train_step


def train_on_blocks(cfg, block_tokens, steps=30, seed=0):
    tc = TrainConfig(total_steps=steps, warmup_steps=3, seed=seed)
    step_fn = jax.jit(make_train_step(cfg, AdamWConfig(lr=5e-3), tc))
    state = init_state(cfg, seed)
    flat = block_tokens.reshape(-1, block_tokens.shape[-1])
    rng = np.random.default_rng(seed)
    for i in range(steps):
        idx = rng.integers(0, flat.shape[0], size=8)
        state, m = step_fn(state, {"tokens": jnp.asarray(flat[idx], jnp.int32)})
    return jax.tree.map(lambda a: a.astype(jnp.float32), state["params"]), float(m["loss"])


def main():
    use_compile_cache()
    cfg = dataclasses.replace(
        ARCHS["llama3.2-1b"],
        num_layers=2, d_model=128, num_heads=4, num_kv_heads=2, d_ff=512,
        vocab_size=512, head_dim=0,
    )
    corpus = make_token_corpus(256, 33, vocab_size=cfg.vocab_size, seed=0)
    spec = RSPSpec(num_records=256, num_blocks=16, num_original_blocks=16, seed=1)
    blocks = two_stage_partition_np(corpus, spec)

    # k base models on disjoint block-level samples
    k = 3
    sampler = BlockSampler(16, seed=2)
    stacked = None
    for i in range(k):
        ids = sampler.sample(4)
        params, loss = train_on_blocks(cfg, blocks[np.asarray(ids)], seed=i)
        print(f"base model {i}: blocks {ids}, final loss {loss:.3f}")
        stacked = (jax.tree.map(lambda a: a[None], params) if stacked is None
                   else jax.tree.map(lambda s, p: jnp.concatenate([s, p[None]]), stacked, params))

    prompts = jnp.asarray(
        np.random.default_rng(9).integers(0, cfg.vocab_size, (4, 8), np.int32)
    )

    single = Server(cfg, jax.tree.map(lambda a: a[0], stacked), ServeConfig())
    t0 = time.time()
    out1 = single.generate(prompts, max_new_tokens=16)
    print(f"single-model batched decode: {out1.shape} in {time.time() - t0:.2f}s")
    print("  sample:", out1[0].tolist())

    ens = EnsembleServer(cfg, stacked, ServeConfig())
    t0 = time.time()
    out2 = ens.generate(prompts, max_new_tokens=16)
    print(f"RSP-ensemble ({k} models) batched decode: {out2.shape} in {time.time() - t0:.2f}s")
    print("  sample:", out2[0].tolist())


if __name__ == "__main__":
    main()
