"""Serving example: batched prefill + autoregressive decode with KV caches,
for any assigned architecture (dense / SWA ring buffer / MoE / Mamba hybrid /
RWKV O(1) state / enc-dec).

    PYTHONPATH=src python examples/serve_decode.py --arch rwkv6-3b --tokens 32
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, get_config
from repro.configs.base import InputShape
from repro.models import build_model, make_batch


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3-8b", choices=list(ARCH_IDS))
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch).reduced()
    model = build_model(cfg, jnp.float32)
    params = model.init(jax.random.PRNGKey(0))
    batch = make_batch(cfg, InputShape("serve", args.prompt_len, args.batch,
                                       "prefill"), dtype=jnp.float32)

    t0 = time.time()
    logits, cache = jax.jit(model.prefill)(params, batch)
    print(f"[{args.arch}] prefill {args.batch}x{args.prompt_len}: "
          f"{time.time()-t0:.2f}s")

    step = jax.jit(model.decode_step)
    key = jax.random.PRNGKey(1)
    tok = jnp.argmax(logits, -1)[:, None]
    generated = [tok]
    t0 = time.time()
    for t in range(args.tokens - 1):
        logits, cache = step(params, tok, cache)
        if args.temperature > 0:
            key, sub = jax.random.split(key)
            tok = jax.random.categorical(sub, logits / args.temperature)[:, None]
        else:
            tok = jnp.argmax(logits, -1)[:, None]
        generated.append(tok)
    dt = time.time() - t0
    gen = jnp.concatenate(generated, axis=1)
    print(f"decoded {args.tokens} tokens/seq x {args.batch} seqs in {dt:.2f}s "
          f"({args.batch * args.tokens / dt:.1f} tok/s)")
    print("sample token ids:", gen[0, :16].tolist())


if __name__ == "__main__":
    from repro.utils.compile_cache import use_compile_cache

    use_compile_cache()
    main()
