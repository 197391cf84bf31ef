"""End-to-end LM training with the quantized delta-aggregation runtime.

Runs the REAL distributed train step (shard_map replicas + mixed-
resolution compressed aggregation) on whatever devices exist — on this
CPU container that is a 1x1 mesh, on a TPU slice the same script uses
the full mesh.  Trains a small decoder on a synthetic Markov token
stream and reports loss + simulated wire traffic; a --preset=100m
configuration matches the deliverable's "~100M model, few hundred
steps" for real hardware.

    PYTHONPATH=src python examples/train_lm_distributed.py \
        --steps 60 --preset tiny
"""
import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.io import save_checkpoint
from repro.data import TokenBatcher, make_token_stream, prefetch
from repro.dist import (CompressorConfig, TrainHParams, build_train_step,
                        microbatch, train_input_shardings)
from repro.launch.mesh import make_mesh
from repro.models import init_model
from repro.models.config import InputShape, ModelConfig

PRESETS = {
    "tiny": dict(num_layers=4, d_model=256, d_ff=704, vocab_size=2048,
                 num_heads=4, num_kv_heads=2, head_dim=64),
    "100m": dict(num_layers=12, d_model=768, d_ff=2048, vocab_size=32768,
                 num_heads=12, num_kv_heads=4, head_dim=64),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="tiny", choices=list(PRESETS))
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--compressor", default="mixed",
                    choices=["mixed", "none"])
    ap.add_argument("--ckpt-dir", default="runs/lm_ckpt")
    args = ap.parse_args()

    cfg = ModelConfig(name=f"lm-{args.preset}", family="dense",
                      **PRESETS[args.preset])
    nd = jax.device_count()
    dm = 1
    mesh = make_mesh((nd // dm, dm), ("data", "model"))
    shape = InputShape("train", seq_len=args.seq,
                       global_batch=args.batch, kind="train")
    hp = TrainHParams(L_local=1, alpha=5e-3,
                      compressor=CompressorConfig(kind=args.compressor,
                                                  s_budget=0.02, bits=8,
                                                  exact_topk=True),
                      remat=False)
    params = init_model(jax.random.PRNGKey(0), cfg)
    n_params = sum(l.size for l in jax.tree_util.tree_leaves(params))
    print(f"model: {n_params/1e6:.1f}M params, mesh {dict(mesh.shape)}")

    step = build_train_step(cfg, mesh, shape, hp)
    stream = make_token_stream(args.batch * (args.seq + 1) * 200,
                               cfg.vocab_size, seed=0)
    batcher = prefetch(iter(
        b for _ in range(100) for b in TokenBatcher(
            stream, args.batch, args.seq)), depth=2)

    b0 = microbatch({"tokens": jnp.zeros((args.batch, args.seq),
                                         jnp.int32)}, hp.L_local)
    ps, bs = train_input_shardings(cfg, mesh, shape, params, b0)
    jstep = jax.jit(step, in_shardings=(ps, bs))

    t0 = time.time()
    for i in range(args.steps):
        host = next(batcher)
        batch = microbatch({"tokens": jnp.asarray(host["tokens"])},
                           hp.L_local)
        params, metrics = jstep(params, batch)
        if i % 10 == 0 or i == args.steps - 1:
            wire = float(metrics["wire_bits_per_replica"]) / 8e6
            print(f"step {i:4d} loss={float(metrics['loss']):.4f} "
                  f"wire={wire:.2f}MB/replica "
                  f"({(time.time()-t0)/(i+1):.2f}s/step)")
    save_checkpoint(args.ckpt_dir, args.steps, params,
                    metadata={"preset": args.preset})
    print(f"saved checkpoint to {args.ckpt_dir}")


if __name__ == "__main__":
    main()
