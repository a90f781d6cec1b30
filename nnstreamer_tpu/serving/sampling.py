"""On-device token sampling for the serving engine.

One traced program covers every request's decoding mode: temperature,
top-k, and nucleus (top-p) controls are per-slot traced VALUES, not
compile-time switches, so a batch can mix greedy and sampled streams in
the same executable (the slot axis is the vmap axis — recompiling per
request mix would defeat continuous batching).

Key schedule: a request's PRNG stream depends only on its own seed and
its absolute consumed-token count (``jax.random.fold_in(seed_key,
consumed)``), never on slot index, batch composition, or chunk size.
That extends the engine's exactness contract to sampled decoding: a
stream's tokens are bit-identical to an isolated single-stream run with
the same seed (tests/test_lm_sampling.py pins it).

Semantics (matching the common serving convention):
- ``temperature <= 0`` → greedy argmax (the key is unused);
- ``top_k <= 0`` → top-k filtering disabled; ties AT the k-th logit are
  all kept (the keep-set can exceed k on exact ties — deterministic);
- ``top_p`` keeps the smallest prefix of the sorted distribution whose
  mass reaches p, applied AFTER top-k; ``top_p >= 1`` or ``<= 0``
  disables it.

The reference has no analog: its NN backends are stateless per-buffer
invokes (`/root/reference/ext/nnstreamer/tensor_filter/`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["sample_row", "sample_logits", "seed_key", "seed_key_host",
           "step_keys"]


def seed_key(seed: int) -> jax.Array:
    """Per-request seed → (2,) uint32 PRNG key (legacy key layout: it
    stores/slots into plain device arrays, which the engine's
    ``_slot_insert`` scatter requires)."""
    return jax.random.PRNGKey(seed)


def seed_key_host(seed: int) -> np.ndarray:
    """:func:`seed_key`'s two words computed on the host, with no device
    program: the seed's high and low 32 bits (the high word is 0 unless
    64-bit types are enabled, as ``PRNGKey`` has it)."""
    hi = (seed >> 32) & 0xFFFFFFFF if jax.config.jax_enable_x64 else 0
    return np.array([hi, seed & 0xFFFFFFFF], np.uint32)


def step_keys(seed_keys: jax.Array, consumed: jax.Array) -> jax.Array:
    """Fold each slot's absolute consumed-token count into its seed key.

    seed_keys (S, 2) uint32; consumed (S,) int32 — the post-step cache
    position, i.e. how many tokens the model has consumed when emitting
    this token. Deterministic in (seed, consumed) only, which is what
    makes batched sampling match isolated sampling.
    """
    return jax.vmap(jax.random.fold_in)(seed_keys, consumed)


def sample_row(logits: jax.Array, key: jax.Array, temperature: jax.Array,
               top_k: jax.Array, top_p: jax.Array) -> jax.Array:
    """Sample one token from one row of logits (V,) → () int32.

    Both filters resolve to ONE value-space threshold computed in sorted
    space (a single O(V log V) top_k per draw — this runs inside the
    decode scan's hot loop), then the categorical draws over the
    ORIGINAL logit order, so a fully-disabled call is bit-identical to
    ``jax.random.categorical(key, logits/T)``. The nucleus mass is
    accumulated over exactly the k top entries; logit TIES at the final
    threshold are all kept (deterministic, may keep a few extra)."""
    v = logits.shape[-1]
    greedy = jnp.argmax(logits, -1)
    scaled = logits.astype(jnp.float32) / jnp.maximum(temperature, 1e-6)
    desc = jax.lax.top_k(scaled, v)[0]
    k_eff = jnp.clip(jnp.where(top_k > 0, top_k, v), 1, v)
    in_k = jnp.arange(v) < k_eff
    p = jax.nn.softmax(jnp.where(in_k, desc, -jnp.inf))
    csum = jnp.cumsum(p)
    p_disabled = ~((top_p > 0.0) & (top_p < 1.0))
    # keep the minimal prefix whose cumulative mass reaches p: position i
    # stays iff the mass BEFORE it is still short of p. A disabled top_p
    # must keep EVERYTHING explicitly — threading p=1.0 through the
    # comparison would still clip the tail once the float32 cumsum
    # saturates at 1.0 (sub-1e-7 probabilities become undrawable,
    # breaking bit-identity with a plain categorical)
    prefix = ((csum - p) < top_p) | p_disabled
    vthresh = jnp.min(jnp.where(prefix & in_k, desc, jnp.inf))
    kept = jnp.where(scaled >= vthresh, scaled, -jnp.inf)
    drawn = jax.random.categorical(key, kept)
    return jnp.where(temperature <= 0.0, greedy, drawn).astype(jnp.int32)


#: (S, V) logits + per-slot (S,)-shaped controls + (S, 2) keys → (S,) tokens
sample_logits = jax.vmap(sample_row)
