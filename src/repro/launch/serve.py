"""Serving launcher: batched prefill+decode requests against an arch.

``python -m repro.launch.serve --arch smollm-360m --requests 4 --new 16``
"""

from __future__ import annotations

import argparse
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new", type=int, default=16)
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--sliding-window", type=int, default=0)
    args = ap.parse_args()

    from repro.launch.compilation import enable_compile_cache

    enable_compile_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.models import build_model
    from repro.serving.engine import Engine, GenerationConfig

    cfg = get_config(args.arch)
    if not args.full:
        cfg = cfg.reduced()
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    engine = Engine(
        api,
        params,
        GenerationConfig(
            max_new_tokens=args.new,
            cache_len=args.prompt_len + args.new,
            sliding_window=args.sliding_window,
        ),
    )
    rng = np.random.default_rng(0)
    if cfg.family == "audio":
        batch = {
            "frames": jnp.asarray(
                rng.standard_normal((args.requests, 32, cfg.d_model), dtype=np.float32) * 0.02
            ),
            "tokens": jnp.asarray(
                rng.integers(0, cfg.vocab_size, size=(args.requests, args.prompt_len))
            ).astype(jnp.int32),
        }
    elif cfg.family == "vlm":
        batch = {
            "tokens": jnp.asarray(
                rng.integers(0, cfg.vocab_size, size=(args.requests, args.prompt_len))
            ).astype(jnp.int32),
            "patch_embeds": jnp.asarray(
                rng.standard_normal(
                    (args.requests, cfg.num_patches, cfg.d_model), dtype=np.float32
                )
                * 0.02
            ),
        }
    else:
        batch = {
            "tokens": jnp.asarray(
                rng.integers(0, cfg.vocab_size, size=(args.requests, args.prompt_len))
            ).astype(jnp.int32)
        }
    t0 = time.time()
    toks, logps = engine.generate(batch)
    dt = time.time() - t0
    print(f"{cfg.name}: generated {toks.shape} in {dt:.1f}s "
          f"({args.requests*args.new/dt:.1f} tok/s)")
    print("sample:", np.asarray(toks[0][:8]))


if __name__ == "__main__":
    main()
