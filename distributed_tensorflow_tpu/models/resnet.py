"""ResNet-50 (ImageNet) — reference workload 2 and the north-star benchmark
(BASELINE.json: "ResNet-50 ImageNet — MultiWorkerMirroredStrategy, sync
allreduce"; metric: images/sec/chip, scaling efficiency 8→256 chips).

TPU-first design notes:

- NHWC layout throughout — flax's native conv layout, and what XLA:TPU maps
  best onto the MXU's (8,128)/(128,128) tiles.
- bf16 compute, f32 master params (``Precision``); BatchNorm mean/var
  reductions, running stats, and the softmax stay f32; BN's elementwise
  normalization runs bf16 (see ``norm_dtype``).
- BatchNorm under global-batch jit is *sync* BatchNorm: the mean/variance
  reductions span the full data-parallel batch and XLA inserts the
  cross-replica collectives.  The reference's MultiWorkerMirroredStrategy
  only ever had per-replica batch stats — this is strictly stronger.
- SGD momentum + label smoothing 0.1, the standard ImageNet recipe the
  reference's train.py would run (TF: tf.keras.optimizers.SGD).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import linen as nn

from distributed_tensorflow_tpu.data.pipeline import synthetic_image_classification
from distributed_tensorflow_tpu.models import Workload
from distributed_tensorflow_tpu.parallel.sharding import ShardingRules

ModuleDef = Any

# uint8 staging quantization for images (records on disk / host->device
# wire): u8 = clip(x * IMG_SCALE + IMG_OFFSET).  Covers roughly x in
# [-4, +4) — ample for normalized image data — at ~1/32 resolution.  Real
# ImageNet pipelines feed uint8 pixels and normalize on device for the same
# reason: the host path (disk, loader memcpy, transfer) is the scarce
# resource, not TPU flops.
IMG_SCALE = 32.0
IMG_OFFSET = 128.0


def quantize_images(batch: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    """Host-side staging transform (Workload.to_record)."""
    out = dict(batch)
    img = np.asarray(batch["image"])
    out["image"] = np.clip(
        np.rint(img * IMG_SCALE + IMG_OFFSET), 0, 255
    ).astype(np.uint8)
    return out


def dequantize_images(batch):
    """Device-side inverse (Workload.from_record), run inside the compiled
    step; no-op for batches that never went through uint8 staging."""
    img = batch["image"]
    if img.dtype != jnp.uint8:
        return batch
    out = dict(batch)
    out["image"] = (img.astype(jnp.float32) - IMG_OFFSET) * (1.0 / IMG_SCALE)
    return out


def augment_images(batch, rng, *, pad: Optional[int] = None):
    """Per-step train augmentation (Workload.augment_fn): random horizontal
    flip + random pad-crop, ON DEVICE inside the compiled step.

    This is the random_crop/random_flip_left_right tf.data map stage of the
    reference's ImageNet input_fn (consumed via input_lib — part of the
    ResNet-50 *recipe*, not a nicety) relocated to where it is cheap on
    TPU: it runs on the raw batch BEFORE ``from_record``, so uint8-staged
    images are flipped/cropped as uint8 (the cheap bytes stay cheap) and
    the host path still moves fixed-size pre-staged tensors.  Fresh
    randomness per step comes from the step rng; eval never calls this
    (train_lib._wrap_from_record wires it train-only).

    Implementation note (measured on v5e-1, batch 256x224^2 uint8, before
    the current chip attachment): the
    textbook composition — bernoulli ``where`` flip, ``jnp.pad(edge)``,
    per-image ``vmap(dynamic_slice)`` — costs 170-316 ms/step (the vmapped
    slice lowers to a pathological gather and the fused uint8 chain
    explodes), which HALVED end-to-end throughput.  Folding flip and edge
    padding INTO the gather indices (flip = reversed column index,
    edge-pad = index clamp) leaves two plain ``take_along_axis`` gathers
    and costs 5.8 ms/step (~5%).  Same math, 30-50x cheaper.
    """
    img = batch["image"]
    B, H, W, C = img.shape
    if pad is None:
        # Shift amplitude scales with resolution (4 px at 224 — the
        # standard ImageNet jitter); a fixed 4 px on a 32 px test image
        # would displace 12% of the frame and wreck tiny-image convergence.
        pad = max(1, round(H / 56))
    r_flip, r_crop = jax.random.split(jax.random.fold_in(rng, 0x0A76))
    flip = jax.random.bernoulli(r_flip, 0.5, (B,))
    offsets = jax.random.randint(r_crop, (B, 2), -pad, pad + 1)
    rows = jnp.clip(offsets[:, 0:1] + jnp.arange(H)[None, :], 0, H - 1)
    cols = jnp.arange(W)[None, :]
    cols = jnp.where(flip[:, None], W - 1 - cols, cols)
    cols = jnp.clip(offsets[:, 1:2] + cols, 0, W - 1)
    img = jnp.take_along_axis(img, rows[:, :, None, None], axis=1)
    img = jnp.take_along_axis(img, cols[:, None, :, None], axis=2)
    out = dict(batch)
    out["image"] = img
    return out


class BottleneckBlock(nn.Module):
    """1x1 -> 3x3 -> 1x1 bottleneck with projection shortcut."""

    filters: int
    strides: Tuple[int, int] = (1, 1)
    dtype: Any = jnp.bfloat16
    norm: ModuleDef = nn.BatchNorm

    @nn.compact
    def __call__(self, x):
        residual = x
        y = nn.Conv(self.filters, (1, 1), use_bias=False, dtype=self.dtype,
                    name="conv1")(x)
        y = self.norm(name="bn1")(y)
        y = nn.relu(y)
        y = nn.Conv(self.filters, (3, 3), self.strides, use_bias=False,
                    dtype=self.dtype, name="conv2")(y)
        y = self.norm(name="bn2")(y)
        y = nn.relu(y)
        y = nn.Conv(4 * self.filters, (1, 1), use_bias=False, dtype=self.dtype,
                    name="conv3")(y)
        # Zero-init the last BN scale so each block starts as identity —
        # standard large-batch ImageNet trick (a training-recipe fact, not a
        # code translation).
        y = self.norm(name="bn3", scale_init=nn.initializers.zeros)(y)

        if residual.shape != y.shape:
            residual = nn.Conv(4 * self.filters, (1, 1), self.strides,
                               use_bias=False, dtype=self.dtype,
                               name="proj_conv")(residual)
            residual = self.norm(name="proj_bn")(residual)

        return nn.relu(residual + y)


class ResNet(nn.Module):
    """ResNet-v1.5 with bottleneck blocks (50/101/152 by stage sizes)."""

    stage_sizes: Sequence[int] = (3, 4, 6, 3)  # ResNet-50
    num_classes: int = 1000
    num_filters: int = 64
    dtype: Any = jnp.bfloat16
    # BN normalization compute dtype.  bf16 measured clearly faster on v5e
    # with an identical loss curve, in a round that predates the current
    # chip attachment; numerically safe
    # because flax's BatchNorm keeps the mean/var reductions and the
    # running batch_stats in f32 regardless of this dtype.
    norm_dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x, train: bool = False):
        norm = functools.partial(
            nn.BatchNorm,
            use_running_average=not train,
            momentum=0.9,
            epsilon=1e-5,
            dtype=self.norm_dtype,
        )
        x = x.astype(self.dtype)
        x = nn.Conv(self.num_filters, (7, 7), (2, 2), padding=[(3, 3), (3, 3)],
                    use_bias=False, dtype=self.dtype, name="conv_init")(x)
        x = norm(name="bn_init")(x)
        x = nn.relu(x)
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding=((1, 1), (1, 1)))
        for i, block_count in enumerate(self.stage_sizes):
            for j in range(block_count):
                strides = (2, 2) if i > 0 and j == 0 else (1, 1)
                x = BottleneckBlock(
                    filters=self.num_filters * 2 ** i,
                    strides=strides,
                    dtype=self.dtype,
                    norm=norm,
                    name=f"stage{i + 1}_block{j + 1}",
                )(x)
        x = jnp.mean(x, axis=(1, 2), dtype=jnp.float32)
        x = nn.Dense(self.num_classes, dtype=jnp.float32, name="logits")(x)
        return x


def _loss_fn(module: nn.Module, label_smoothing: float, params, model_state,
             batch: Dict[str, jax.Array], rng):
    logits, new_vars = module.apply(
        {"params": params, **model_state},
        batch["image"],
        train=True,
        mutable=["batch_stats"],
    )
    labels = batch["label"]
    num_classes = logits.shape[-1]
    onehot = jax.nn.one_hot(labels, num_classes, dtype=jnp.float32)
    smoothed = onehot * (1 - label_smoothing) + label_smoothing / num_classes
    loss = jnp.mean(
        optax.softmax_cross_entropy(logits.astype(jnp.float32), smoothed)
    )
    acc = jnp.mean((jnp.argmax(logits, -1) == labels).astype(jnp.float32))
    return loss, {"accuracy": acc}, dict(new_vars)


def _eval_loss_fn(module: nn.Module, params, model_state,
                  batch: Dict[str, jax.Array], rng):
    """Inference mode: BatchNorm uses the running averages (train=False)."""
    logits = module.apply(
        {"params": params, **model_state}, batch["image"], train=False,
    )
    labels = batch["label"]
    loss = jnp.mean(
        optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), labels
        )
    )
    acc = jnp.mean((jnp.argmax(logits, -1) == labels).astype(jnp.float32))
    return loss, {"accuracy": acc}, model_state


def make_workload(
    *,
    batch_size: int = 1024,
    num_classes: int = 1000,
    image_size: int = 224,
    stage_sizes: Sequence[int] = (3, 4, 6, 3),
    learning_rate: float = 0.1,  # scaled by batch/256 in the classic recipe
    augment: bool = True,  # per-step device-side crop+flip (the recipe);
    # False for short-horizon convergence tests where per-step view
    # variance swamps an 8-step loss-decrease assertion
    **_unused,
) -> Workload:
    module = ResNet(stage_sizes=tuple(stage_sizes), num_classes=num_classes)
    return Workload(
        name="resnet50",
        module=module,
        loss_fn=functools.partial(_loss_fn, module, 0.1),
        init_batch={
            "image": np.zeros((2, image_size, image_size, 3), np.float32),
            "label": np.zeros((2,), np.int32),
        },
        data_fn=lambda per_host_bs: synthetic_image_classification(
            batch_size=per_host_bs,
            image_size=(image_size, image_size, 3),
            num_classes=num_classes,
        ),
        eval_data_fn=lambda per_host_bs: synthetic_image_classification(
            batch_size=per_host_bs,
            image_size=(image_size, image_size, 3),
            num_classes=num_classes, holdout=True,
        ),
        # Pure DP is the reference's ResNet-50 mode (sync allreduce); conv
        # kernels are small relative to activations so replication is right.
        rules=ShardingRules(),
        batch_size=batch_size,
        learning_rate=learning_rate * batch_size / 256,
        warmup_steps=500,
        clip_grad_norm=None,
        example_key="image",
        init_key="image",
        stateful=True,
        eval_loss_fn=functools.partial(_eval_loss_fn, module),
        make_optimizer=lambda schedule: optax.sgd(
            schedule, momentum=0.9, nesterov=True
        ),
        to_record=quantize_images,
        from_record=dequantize_images,
        augment_fn=augment_images if augment else None,
    )
