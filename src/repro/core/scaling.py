"""Sparsity-pattern statistics behind FSVRG's S_k and A matrices (§3.6.1).

  n^j   — #examples with nonzero coordinate j
  n_k^j — #examples on client k with nonzero coordinate j
  φ^j   = n^j / n,   φ_k^j = n_k^j / n_k
  s_k^j = φ^j / φ_k^j           (stochastic-gradient scaling, S_k = Diag)
  ω^j   — #clients containing coordinate j
  a^j   = K / ω^j               (aggregation scaling, A = Diag)

S_k is computed *on the fly* inside each client pass (a (d,) scatter per
client) so full-scale K×d storage is never materialized; ω/A are global and
precomputed once here.

``expert_occupancy`` is the MoE analogue used by the federated-LLM bridge:
which experts a client's tokens route to plays the role of which features a
client's examples touch.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def global_feature_counts(flat) -> jax.Array:
    """n^j for a LogRegProblem (or a VirtualFlat, which streams the count
    over regenerated client chunks — integer sums, so the two layouts
    agree exactly)."""
    if hasattr(flat, "feature_counts"):
        return flat.feature_counts()
    present = (flat.val != 0).astype(jnp.float32)
    return jnp.zeros((flat.num_features,)).at[flat.idx].add(present)


def client_feature_counts(idx, val, num_features) -> jax.Array:
    """n_k^j for one client's (m, nnz) rows (padded rows have val==0)."""
    present = (val != 0).astype(jnp.float32)
    return jnp.zeros((num_features,)).at[idx].add(present)


def omega(problem) -> jax.Array:
    """ω^j — #clients whose data touches coordinate j.  Virtual problems
    stream the count over regenerated chunks (exact, same integer sums)."""
    if getattr(problem, "virtual", None) is not None:
        return problem.flat.omega()
    from repro.core.problem import map_clients

    d = problem.d
    present = jax.vmap(lambda i, v: client_feature_counts(i, v, d) > 0)
    om = jnp.zeros((d,))
    for b in problem.buckets:
        cc = map_clients(present, (b.idx, b.val), d)
        om = om + cc.sum(axis=0).astype(jnp.float32)
    return om


def aggregation_diag(problem) -> jax.Array:
    """A = Diag(K / ω^j); coordinates on no client get a^j = 1."""
    om = omega(problem)
    K = problem.num_clients
    return jnp.where(om > 0, K / jnp.maximum(om, 1.0), 1.0)


def s_k_diag(phi_global: jax.Array, idx, val, n_k) -> jax.Array:
    """s_k^j = φ^j / φ_k^j for one client; 1 where the client lacks j."""
    d = phi_global.shape[0]
    nkj = client_feature_counts(idx, val, d)
    phi_k = nkj / jnp.maximum(n_k.astype(jnp.float32), 1.0)
    return jnp.where(nkj > 0, phi_global / jnp.maximum(phi_k, 1e-12), 1.0)


def expert_occupancy(router_probs: jax.Array, top_k: int) -> jax.Array:
    """MoE analogue of n_k^j: which experts this client's tokens route to.

    router_probs: (tokens, E) softmax router outputs for one client's batch.
    Returns (E,) counts of tokens whose top-k includes each expert.
    """
    E = router_probs.shape[-1]
    _, topi = jax.lax.top_k(router_probs, top_k)
    onehot = jax.nn.one_hot(topi, E).sum(axis=1)
    return onehot.sum(axis=0)
