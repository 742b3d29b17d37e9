"""Plain reference of DeepFM (arXiv:1703.04247) as the repo's zoo
module computes it: first-order term, FM second-order term and a ReLU
tower over the flattened field embeddings, summed into one logit, and
the mean sigmoid cross-entropy. ``jax.numpy`` only; it imports nothing
from ``elasticdl_tpu``. The embedding rows are inputs here, as they are
on the device (the parameter servers own the tables)."""

import jax
import jax.numpy as jnp


def logits(dense, emb_rows, linear_rows, ids):
    """ids: (B, F) positions into the pulled rows. ``dense``: the
    tower's kernels and biases, ``Dense_0`` .. in order."""
    emb = emb_rows[ids]  # (B, F, d)
    first = linear_rows[ids][..., 0].sum(axis=1)
    summed = emb.sum(axis=1)
    second = 0.5 * (summed ** 2 - (emb ** 2).sum(axis=1)).sum(axis=-1)
    deep = emb.reshape((emb.shape[0], -1))
    layers = sorted(dense, key=lambda name: int(name.rsplit("_", 1)[1]))
    for name in layers[:-1]:
        deep = jax.nn.relu(deep @ dense[name]["kernel"] + dense[name]["bias"])
    last = dense[layers[-1]]
    return first + second + (deep @ last["kernel"] + last["bias"])[:, 0]


def loss(labels, z):
    """Mean of -[y log s(z) + (1 - y) log(1 - s(z))]."""
    return jnp.mean(
        jnp.maximum(z, 0) - z * labels + jnp.log1p(jnp.exp(-jnp.abs(z))))
