"""End-to-end driver: federated training of a ~100M-parameter language model
with FSVRG rounds (the paper's technique as a first-class framework feature).

Clients are synthetic non-IID token streams — each client has a private
token distribution (the LM analogue of the paper's per-author vocabulary) —
and the round applies per-vocab-row S_k/A scaling exactly as Algorithm 4
prescribes for sparse features.

    PYTHONPATH=src python examples/federated_lm.py [--rounds 200] [--arch llama3-8b]

By default trains a ~100M reduced variant of the chosen architecture for a
few hundred rounds on CPU.
"""
import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core import neural
from repro.models import build_model


def synthetic_federated_tokens(rng, num_clients, batch_per_client, seq_len,
                               vocab, steps_per_client):
    """Each client samples from its own zipf-reweighted vocabulary slice."""
    out = []
    base = 1.0 / (np.arange(2, vocab) ** 1.05)
    for k in range(num_clients):
        own = rng.choice(np.arange(2, vocab), size=max(8, vocab // 50),
                         replace=False)
        p = base.copy()
        p[own - 2] *= 50.0                      # client-specific skew
        p = np.concatenate([[0.02, 0.02], p / p.sum() * 0.96])
        p = p / p.sum()
        toks = rng.choice(vocab, size=(steps_per_client, batch_per_client,
                                       seq_len + 1), p=p)
        out.append(toks)
    return np.stack(out)                        # (C, T, B_c, S+1)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3-8b")
    ap.add_argument("--rounds", type=int, default=200)
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--local-steps", type=int, default=2)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch-per-client", type=int, default=4)
    ap.add_argument("--stepsize", type=float, default=0.5)
    ap.add_argument("--eval-every", type=int, default=10)
    args = ap.parse_args(argv)

    # ~100M-class variant: reduced depth/width but real vocab structure
    cfg = get_config(args.arch).reduced()
    cfg = dataclasses.replace(cfg, name=cfg.name + "-100m", num_layers=4,
                              d_model=256, d_ff=1024, vocab_size=8192,
                              num_heads=4, num_kv_heads=2, head_dim=64)
    model = build_model(cfg, jnp.float32)
    params = model.init(jax.random.PRNGKey(0))
    n_params = sum(p.size for p in jax.tree.leaves(params))
    print(f"arch={cfg.name} params={n_params/1e6:.1f}M "
          f"C={args.clients} T={args.local_steps} seq={args.seq}")

    rng = np.random.default_rng(0)
    rnd = jax.jit(neural.make_fsvrg_round(
        model, neural.FedNeuralConfig(stepsize=args.stepsize,
                                      local_steps=args.local_steps)))

    held_out = None
    t0 = time.time()
    for r in range(args.rounds):
        toks = synthetic_federated_tokens(
            rng, args.clients, args.batch_per_client, args.seq,
            cfg.vocab_size, args.local_steps)
        cb = {
            "tokens": jnp.asarray(toks[:, :, :, :-1], jnp.int32),
            "labels": jnp.asarray(toks[:, :, :, 1:], jnp.int32),
            "mask": jnp.ones(toks[:, :, :, 1:].shape, jnp.float32),
        }
        if held_out is None:
            held_out = jax.tree.map(lambda x: x[0, 0], cb)   # client-0 batch
        params, metrics = rnd(params, cb)
        if (r + 1) % args.eval_every == 0 or r == 0:
            loss = float(model.loss(params, held_out)[0])
            print(f"round {r+1:4d}: held-out loss={loss:.4f} "
                  f"|∇f|={float(metrics['full_grad_norm']):.4f} "
                  f"({time.time()-t0:.0f}s)")

    final = float(model.loss(params, held_out)[0])
    print(f"done: final held-out loss {final:.4f} "
          f"(random-init would be ~{np.log(cfg.vocab_size):.2f})")
    return final


if __name__ == "__main__":
    from repro.utils.compile_cache import use_compile_cache

    use_compile_cache()
    main()
