"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

On this CPU container it trains the *reduced* variant of any assigned
architecture on the synthetic stream (host mesh); on a real pod the same
entry point takes ``--full --mesh single|multi`` and runs the production
mesh with the dry-run's shardings.
"""

from __future__ import annotations

import argparse
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--full", action="store_true", help="full (non-reduced) config")
    ap.add_argument("--ckpt", default=None)
    args = ap.parse_args()

    from repro.launch.compilation import enable_compile_cache

    enable_compile_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.models import build_model
    from repro.training import (
        AdamWConfig,
        DataConfig,
        MarkovTextStream,
        init_train_state,
        make_train_step,
        save_checkpoint,
    )

    cfg = get_config(args.arch)
    if not args.full:
        cfg = cfg.reduced()
    api = build_model(cfg)
    print(f"{cfg.name}: {api.param_count()/1e6:.1f}M params ({cfg.family})")

    state = init_train_state(api, jax.random.PRNGKey(0))
    opt = AdamWConfig(lr=1e-3, warmup_steps=10, total_steps=args.steps)
    step = jax.jit(make_train_step(api, opt))

    rng = np.random.default_rng(0)
    stream = MarkovTextStream(
        DataConfig(cfg.vocab_size, args.seq, args.batch, seed=0)
    )
    t0 = time.time()
    for i, raw in zip(range(args.steps), stream):
        toks = jnp.asarray(raw["tokens"][:, : args.seq])
        if cfg.family == "audio":
            batch = {
                "frames": jnp.asarray(
                    rng.standard_normal((args.batch, 32, cfg.d_model), dtype=np.float32) * 0.02
                ),
                "tokens": toks[:, :16],
            }
        elif cfg.family == "vlm":
            batch = {
                "tokens": toks,
                "patch_embeds": jnp.asarray(
                    rng.standard_normal(
                        (args.batch, cfg.num_patches, cfg.d_model), dtype=np.float32
                    )
                    * 0.02
                ),
            }
        else:
            batch = {"tokens": toks}
        state, m = step(state, batch)
        if i % 10 == 0 or i == args.steps - 1:
            print(
                f"step {i:4d} loss {float(m['loss']):.3f} "
                f"({(time.time()-t0)/(i+1):.2f}s/step)",
                flush=True,
            )
    if args.ckpt:
        save_checkpoint(args.ckpt, state.params, step=args.steps)
        print(f"saved {args.ckpt}")


if __name__ == "__main__":
    main()
