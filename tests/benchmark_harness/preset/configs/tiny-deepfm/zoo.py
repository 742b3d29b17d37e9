"""The repo's DeepFM zoo module plus the benchmark's probe: what a
``model_config`` PR would add for a CTR cell, at a rehearsal's size."""

from benchmark.lib.probe import callbacks  # noqa: F401
from elasticdl_tpu.models.deepfm import (  # noqa: F401
    custom_model,
    dataset_fn,
    loss,
    optimizer,
    sparse_embedding_specs,
)
