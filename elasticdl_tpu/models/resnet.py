"""ResNet v1.5 family (ResNet18/34/50/101/152) in flax.

Reference parity: model_zoo/imagenet_resnet50/, model_zoo/cifar10/ and
model_zoo/resnet50_subclass/ (Keras applications-based). Fresh TPU-first
implementation: NHWC layout (TPU conv-native), TpuBatchNorm
(ops/batch_norm.py: f32 single-pass statistics, residual stream stays
in the compute dtype — a BN that forced f32 outputs would promote every
downstream conv to f32 and halve the MXU rate; the single-pass stats
and the fused multiply-add normalize read the activation once where
flax's nn.BatchNorm reads it twice);
zero-init on the last BN scale of each block (standard trick: the
residual branch starts as identity, which stabilizes large-batch
training), and channel counts that are multiples of 128 in the deep
stages so the MXU tiles cleanly.
"""

from functools import partial
from typing import Sequence

import flax.linen as nn
import jax.numpy as jnp
import numpy as np

from elasticdl_tpu.data.example import decode_example
from elasticdl_tpu.ops.batch_norm import TpuBatchNorm
from elasticdl_tpu.train import metrics
from elasticdl_tpu.train.losses import sparse_softmax_cross_entropy
from elasticdl_tpu.train.optimizers import create_optimizer


class BottleneckBlock(nn.Module):
    filters: int
    strides: int = 1

    @nn.compact
    def __call__(self, x, training: bool = False):
        norm = partial(
            TpuBatchNorm,
            use_running_average=not training,
            momentum=0.9,
            epsilon=1e-5,
        )
        residual = x
        y = nn.Conv(self.filters, (1, 1), use_bias=False)(x)
        y = norm()(y)
        y = nn.relu(y)
        y = nn.Conv(
            self.filters, (3, 3), strides=(self.strides, self.strides),
            padding=[(1, 1), (1, 1)], use_bias=False,
        )(y)
        y = norm()(y)
        y = nn.relu(y)
        y = nn.Conv(self.filters * 4, (1, 1), use_bias=False)(y)
        y = norm(scale_init=nn.initializers.zeros_init())(y)
        if residual.shape[-1] != self.filters * 4 or self.strides != 1:
            residual = nn.Conv(
                self.filters * 4,
                (1, 1),
                strides=(self.strides, self.strides),
                use_bias=False,
            )(x)
            residual = norm()(residual)
        return nn.relu(y + residual)


class BasicBlock(nn.Module):
    filters: int
    strides: int = 1

    @nn.compact
    def __call__(self, x, training: bool = False):
        norm = partial(
            TpuBatchNorm,
            use_running_average=not training,
            momentum=0.9,
            epsilon=1e-5,
        )
        residual = x
        y = nn.Conv(
            self.filters, (3, 3), strides=(self.strides, self.strides),
            padding=[(1, 1), (1, 1)], use_bias=False,
        )(x)
        y = norm()(y)
        y = nn.relu(y)
        y = nn.Conv(self.filters, (3, 3), padding=[(1, 1), (1, 1)], use_bias=False)(y)
        y = norm(scale_init=nn.initializers.zeros_init())(y)
        if residual.shape[-1] != self.filters or self.strides != 1:
            residual = nn.Conv(
                self.filters,
                (1, 1),
                strides=(self.strides, self.strides),
                use_bias=False,
            )(x)
            residual = norm()(residual)
        return nn.relu(y + residual)


def space_to_depth(x, block=2):
    """[B, H, W, C] -> [B, H/b, W/b, C*b*b]: each output pixel packs a
    b x b spatial block into channels. Pure reshape/transpose — free on
    TPU relative to an HBM-bound stem conv."""
    b, h, w, c = x.shape
    x = x.reshape(b, h // block, block, w // block, block, c)
    x = x.transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(b, h // block, w // block, c * block * block)


class ResNet(nn.Module):
    stage_sizes: Sequence[int]
    block_cls: type = BottleneckBlock
    num_classes: int = 1000
    num_filters: int = 64
    small_inputs: bool = False  # cifar-style stem (3x3, no maxpool)
    # "conv7": the classic 7x7/2 stem. "space_to_depth": MLPerf-style
    # conv0 — input packed 2x2 into channels, then a 4x4/1 conv on the
    # half-res grid; same receptive-field class (7x7 zero-padded to 8x8
    # factorizes exactly over 2x2 blocks), far better MXU utilization
    # than a stride-2 conv over 3 channels.
    stem: str = "conv7"

    @nn.compact
    def __call__(self, x, training: bool = False):
        if self.stem not in ("conv7", "space_to_depth"):
            raise ValueError(
                "unknown stem %r (conv7 | space_to_depth)" % self.stem
            )
        if self.small_inputs and self.stem != "conv7":
            raise ValueError(
                "small_inputs uses the cifar 3x3 stem; stem=%r conflicts"
                % self.stem
            )
        if x.ndim == 3:
            x = x[..., None]
        if self.small_inputs:
            x = nn.Conv(
                self.num_filters, (3, 3), padding=[(1, 1), (1, 1)],
                use_bias=False,
            )(x)
        elif self.stem == "space_to_depth":
            x = space_to_depth(x, 2)
            x = nn.Conv(
                self.num_filters, (4, 4), padding="SAME", use_bias=False
            )(x)
        else:
            x = nn.Conv(
                self.num_filters, (7, 7), strides=(2, 2),
                padding=[(3, 3), (3, 3)], use_bias=False,
            )(x)
        x = TpuBatchNorm(
            use_running_average=not training,
            momentum=0.9,
            epsilon=1e-5,
        )(x)
        x = nn.relu(x)
        if not self.small_inputs:
            x = nn.max_pool(
                x, (3, 3), strides=(2, 2), padding=[(1, 1), (1, 1)]
            )
        for stage, num_blocks in enumerate(self.stage_sizes):
            for block in range(num_blocks):
                strides = 2 if stage > 0 and block == 0 else 1
                x = self.block_cls(
                    filters=self.num_filters * 2**stage, strides=strides
                )(x, training=training)
        x = jnp.mean(x, axis=(1, 2))
        return nn.Dense(self.num_classes, dtype=jnp.float32)(x)


def resnet18(num_classes=1000, **kwargs):
    return ResNet([2, 2, 2, 2], BasicBlock, num_classes, **kwargs)


def resnet34(num_classes=1000, **kwargs):
    return ResNet([3, 4, 6, 3], BasicBlock, num_classes, **kwargs)


def resnet50(num_classes=1000, **kwargs):
    return ResNet([3, 4, 6, 3], BottleneckBlock, num_classes, **kwargs)


def resnet101(num_classes=1000, **kwargs):
    return ResNet([3, 4, 23, 3], BottleneckBlock, num_classes, **kwargs)


def resnet152(num_classes=1000, **kwargs):
    return ResNet([3, 8, 36, 3], BottleneckBlock, num_classes, **kwargs)


# ---------------------------------------------------------------------
# model-zoo contract (imagenet_resnet50 equivalent)

NUM_CLASSES = 1000


def custom_model():
    return resnet50(num_classes=NUM_CLASSES)


def loss(labels, predictions):
    return sparse_softmax_cross_entropy(labels, predictions)


def optimizer():
    return create_optimizer(
        "Momentum", learning_rate=0.1, momentum=0.9, nesterov=True
    )


def dataset_fn(dataset, mode=None, metadata=None):
    def parse(payload):
        example = decode_example(payload)
        image = example["image"].astype(np.float32) / 255.0
        label = example["label"].astype(np.int32).reshape(())
        return image, label

    return dataset.map(parse)


def eval_metrics_fn():
    return {"accuracy": metrics.Accuracy()}
