"""Block diffusion's training objective (BD3-LMs, arXiv:2503.09573, over
the masked-diffusion loss of MDLM, arXiv:2406.07524; SDAR,
arXiv:2510.06303, trains its models with it): the noise, the two copies
a step runs, and the weighted loss. Pure functions on the device; the
mask that goes with them is ``ops/flash_attention.py:BlockDiffusion``.

For a sequence ``x_0`` of L tokens in blocks of B:

    t_b ~ U(t_min, 1) a block b;   m_i ~ Bernoulli(t_blk(i)) a token i
    x_t,i = [MASK] if m_i else x_0,i;   w_i = m_i / t_blk(i)
    input: [x_t ; x_0], 2 L positions, position p rotating by p mod L
    loss:  (1 / L) sum_i w_i CE(logits_i of the noisy half, x_0,i)

which is the linear schedule's bound (a token is masked with
probability t; the weight of a masked token is 1 / t), so that ``sum(w)
/ L`` averages 1. ``noise`` is a function of its key alone: the
benchmark's reference check draws the same ``x_t`` and ``w`` by calling
it with the same key.
"""

import jax
import jax.numpy as jnp

from elasticdl_tpu.train.losses import sparse_softmax_cross_entropy

# the scopes a device trace reads this layer by
# (benchmark/lib/bd_trace.py, docs/OBSERVABILITY.md)
NOISE_SCOPE = "bd/noise"
ASSEMBLE_SCOPE = "bd/assemble"


def noise_levels(key, batch_shape, num_blocks, t_min):
    """``t`` (..., blocks): one noise level a block, U(t_min, 1)."""
    level_key, _ = jax.random.split(key)
    return jax.random.uniform(
        level_key, tuple(batch_shape) + (num_blocks,), jnp.float32,
        minval=t_min, maxval=1.0)


def noise(key, tokens, block, mask_id, t_min):
    """``(x_t, w)`` for ``tokens`` (..., L): the noisy copy (a token of
    block b replaced by ``mask_id`` with probability ``t_b``) and the
    loss's weights (``1 / t_b`` where the token was masked, 0
    elsewhere), float32. The clean copy is ``tokens`` itself, which
    this never touches."""
    with jax.named_scope(NOISE_SCOPE):
        length = tokens.shape[-1]
        if length % block:
            raise ValueError(
                "blocks of %d tokens do not divide a sequence of %d"
                % (block, length))
        levels = jnp.repeat(
            noise_levels(key, tokens.shape[:-1], length // block, t_min),
            block, axis=-1)
        _, mask_key = jax.random.split(key)
        masked = jax.random.uniform(
            mask_key, tokens.shape, jnp.float32) < levels
        x_t = jnp.where(masked, jnp.asarray(mask_id, tokens.dtype), tokens)
        return x_t, jnp.where(masked, 1.0 / levels, 0.0)


def noise_facts(key, weights, block, t_min):
    """What the ``bd_noise`` event carries of a step's noise: the share
    of the tokens that were masked, the mean noise level of the blocks
    (drawn again from the key: the same numbers) and ``sum(w) / L``,
    which averages 1."""
    with jax.named_scope(NOISE_SCOPE):
        levels = noise_levels(
            key, weights.shape[:-1], weights.shape[-1] // block, t_min)
        return {
            "masked_share": jnp.mean((weights > 0).astype(jnp.float32)),
            "mean_t": jnp.mean(levels),
            "weight_mean": jnp.mean(weights),
        }


def assemble(noisy, clean):
    """``([x_t ; x_0] (..., 2 L), positions (2 L,))``: the two copies
    side by side and what each row rotates by, ``p mod L``."""
    with jax.named_scope(ASSEMBLE_SCOPE):
        length = clean.shape[-1]
        positions = jnp.tile(jnp.arange(length, dtype=jnp.int32), 2)
        return jnp.concatenate([noisy, clean], axis=-1), positions


def noisy_half(x):
    """The noisy copy's rows of ``x`` (B, 2 L, d): the only ones whose
    logits enter the loss."""
    with jax.named_scope(ASSEMBLE_SCOPE):
        return x[:, :x.shape[1] // 2]


def weighted_loss(targets, logits, weights):
    """``(1 / L) sum_i w_i CE(logits_i, targets_i)`` a sample:
    position-aligned (logits at i predict the clean token AT i, no
    shift), float32."""
    per_token = sparse_softmax_cross_entropy(targets, logits)
    return (per_token.astype(jnp.float32) * weights).mean(axis=-1)
