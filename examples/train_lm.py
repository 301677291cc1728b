"""Train a ~130M-parameter LM for a few hundred steps on the synthetic
pipeline — exercises the full training substrate (optimizer, remat, ckpt,
deterministic resume) on one host.

    PYTHONPATH=src python examples/train_lm.py --steps 300
    PYTHONPATH=src python examples/train_lm.py --steps 40 --tiny   # CI-sized
"""
import sys
sys.path.insert(0, "src")

import argparse
import os
import time
from dataclasses import replace

import jax

from repro.models.config import ModelConfig, param_count
from repro.models import lm
from repro.train import checkpoint, compression, data
from repro.train import optimizer as opt
from repro.train import train_step as ts

LM_130M = ModelConfig(
    name="repro-130m", n_layers=10, d_model=640, n_heads=10, n_kv=10,
    d_ff=2560, vocab=50048, head_dim=64, norm="rmsnorm", mlp="swiglu",
    remat=False)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--ckpt", default="/tmp/repro_train_lm")
    args = ap.parse_args()

    cfg = LM_130M if not args.tiny else replace(
        LM_130M, n_layers=2, d_model=128, n_heads=4, n_kv=4, d_ff=512,
        vocab=1024, head_dim=32)
    print(f"{cfg.name}: {param_count(cfg)/1e6:.0f}M params")
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    ocfg = opt.AdamWConfig(lr=6e-4, warmup_steps=max(10, args.steps // 10),
                           total_steps=args.steps)
    state = opt.init_state(params)
    err = compression.init_error(params)
    step_fn = jax.jit(ts.make_train_step(cfg, ocfg))
    stream = data.TokenStream(data.DataConfig(
        vocab=cfg.vocab, seq_len=args.seq, global_batch=args.batch))

    os.makedirs(args.ckpt, exist_ok=True)
    start = checkpoint.latest_step(args.ckpt) or 0
    if start:
        params, state, start, extra = checkpoint.restore(
            args.ckpt, start, params, state)
        stream.load_state_dict(extra["data"])
        print(f"resumed at step {start}")

    first = None
    for step in range(start, args.steps):
        t0 = time.time()
        params, state, err, m = step_fn(params, state, err, next(stream))
        if first is None:
            first = float(m["loss"])
        if step % 20 == 0 or step == args.steps - 1:
            print(f"step {step:4d} loss {float(m['loss']):.4f} "
                  f"({(time.time()-t0)*1e3:.0f} ms)", flush=True)
        if (step + 1) % 100 == 0:
            checkpoint.save(args.ckpt, step + 1, params, state,
                            extra={"data": stream.state_dict()})
    print(f"loss: {first:.3f} -> {float(m['loss']):.3f}")
    assert float(m["loss"]) < first, "training must reduce loss"


if __name__ == "__main__":
    from repro.core.backend import enable_compile_cache
    enable_compile_cache()
    main()
