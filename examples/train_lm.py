"""LM training driver on the shared runtime (any --arch from the zoo).

Reduced configs run on CPU; full configs are for the TPU meshes (use
launch/dryrun.py to validate those).  Demonstrates the fault-tolerant
trainer: kill it mid-run and rerun the same command — it resumes.

    PYTHONPATH=src python examples/train_lm.py --arch olmo-1b --steps 100
    PYTHONPATH=src python examples/train_lm.py --arch olmo-1b --size 100m --steps 200
"""

import argparse
import dataclasses

import jax
import jax.numpy as jnp

from repro import configs
from repro.compile_cache import use_persistent_cache
from repro.data.synthetic import token_batches
from repro.models import transformer as tf
from repro.optim import Adam, cosine_warmup
from repro.train.train_step import make_train_step
from repro.train.trainer import Trainer


def build_config(arch: str, size: str):
    if size == "smoke":
        return configs.get_smoke_config(arch)
    if size == "100m":
        # ~100M-parameter variant of the chosen family
        base = configs.get_smoke_config(arch)
        return dataclasses.replace(
            base,
            n_layers=max(8, len(base.pattern) * 4),
            d_model=512,
            n_heads=8,
            n_kv_heads=min(8, max(base.n_kv_heads, 2)),
            head_dim=64,
            d_ff=2048 if base.d_ff else 0,
            moe_d_ff=512 if base.n_experts else 0,
            vocab_size=32768,
            rnn_width=512 if base.rnn_width else None,
        )
    return configs.get_config(arch)


def main():
    use_persistent_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b", choices=list(configs.ARCH_IDS))
    ap.add_argument("--size", default="smoke", choices=["smoke", "100m", "full"])
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    args = ap.parse_args()

    cfg = build_config(args.arch, args.size)
    if cfg.input_mode == "embeddings":
        raise SystemExit(f"{args.arch} takes stub embeddings; use the dry-run for it")
    print(f"arch={cfg.name} params≈{cfg.param_count()/1e6:.1f}M "
          f"(active {cfg.active_param_count()/1e6:.1f}M)")

    params = tf.init_model(jax.random.PRNGKey(0), cfg)
    opt = Adam(learning_rate=cosine_warmup(args.lr, args.steps // 10, args.steps))
    step_fn, _ = make_train_step(cfg, opt, donate=False)

    def data_fn(step):
        t, l = next(token_batches(cfg.vocab_size, args.batch, args.seq, seed=step))
        return jnp.asarray(t), jnp.asarray(l)

    trainer = Trainer(
        step_fn, params, opt.init(params), data_fn,
        ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every, log_every=10,
    )
    rep = trainer.run(args.steps)
    print(
        f"done: {rep.steps} steps, loss {rep.losses[0]:.4f} -> {rep.last_loss:.4f}, "
        f"median step {rep.median_step_time()*1e3:.1f} ms, "
        f"stragglers {rep.stragglers}"
    )


if __name__ == "__main__":
    main()
