"""Test model-zoo module: mnist whose training outputs carry a fact
beside the logits, under a key the program's table does not have
(``tests/test_trainer_contract.py`` adds the row)."""

import jax.numpy as jnp

from elasticdl_tpu.models.mnist import (  # noqa: F401
    MnistCNN,
    dataset_fn,
    eval_metrics_fn,
    optimizer,
)
from elasticdl_tpu.models.mnist import loss as _loss

FACT_KEY = "probe"


class MnistWithFact(MnistCNN):
    def __call__(self, x, training: bool = False):
        logits = super().__call__(x, training)
        if not training:
            return logits
        return {"logits": logits, FACT_KEY: {
            "rows": jnp.float32(logits.shape[0]),
            "class_mean": logits.mean(axis=0)}}


def custom_model():
    return MnistWithFact()


def loss(labels, outputs):
    return _loss(labels, outputs["logits"])
