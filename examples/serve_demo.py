"""Serving demo: batched prefill + greedy decode with every cache variety in
the zoo (KV cache, MLA latent cache, mamba/xLSTM recurrent state), on reduced
configs. The identical serve_step lowers for decode_32k / long_500k on the
production mesh.

  PYTHONPATH=src python examples/serve_demo.py [--arch jamba-1.5-large-398b]
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.models import lm
from repro.training.steps import make_serve_step


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="jamba-1.5-large-398b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=24)
    args = ap.parse_args()

    cfg = get_config(args.arch).reduced()
    rng = jax.random.PRNGKey(0)
    params = lm.init_params(cfg, rng)
    B, K = args.batch, args.prompt_len
    cache_len = K + args.gen

    batch = {"tokens": jax.random.randint(rng, (B, K), 0, cfg.vocab_size)}
    if cfg.is_encoder_decoder:
        batch["frames"] = jax.random.normal(
            rng, (B, cfg.num_encoder_positions, cfg.d_model))
    if cfg.num_vision_patches:
        batch["patches"] = jax.random.normal(
            rng, (B, cfg.num_vision_patches, cfg.d_model))
    P = cfg.num_vision_patches or 0

    print(f"arch={args.arch} (reduced) — prefill {K} tokens x{B}, "
          f"decode {args.gen}")
    t0 = time.time()
    last, cache = jax.jit(
        lambda p, b: lm.prefill(cfg, p, b, cache_len + P))(params, batch)
    print(f"  prefill: {time.time()-t0:.2f}s")

    serve = jax.jit(make_serve_step(cfg))
    tok = jnp.argmax(last, axis=-1).astype(jnp.int32)
    out = [tok]
    t0 = time.time()
    for i in range(args.gen - 1):
        tok, logits, cache = serve(params, cache, tok, jnp.int32(P + K + i))
        out.append(tok)
    dt = time.time() - t0
    toks = jnp.stack(out, axis=1)
    print(f"  decode: {args.gen-1} steps in {dt:.2f}s "
          f"({B*(args.gen-1)/max(dt,1e-9):.1f} tok/s batch-aggregate)")
    print(f"  sample continuation (client 0): {toks[0].tolist()}")


if __name__ == "__main__":
    main()
