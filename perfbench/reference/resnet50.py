"""Plain reference of ResNet-50's training loss: float32 ``jax.numpy``,
convolutions at ``highest`` precision, batch statistics computed in the
open.

It reads the parameter tree of ``horovod_tpu.models.ResNet50`` and
follows He et al. 2015 in the v1.5 form (stride in the 3x3 of a
bottleneck), as the reference benchmark's Keras ``ResNet50`` and every
later framework do. Departure of the repo's model, which the reference
follows: convolutions pad ``SAME`` in the TensorFlow sense (a stride-2
3x3 on an even size pads 0 before and 1 after; He et al. pad 1 and 1).
Training mode: each norm uses the batch's own mean and variance, so the
loss depends on the whole batch and the reference takes all of it.
"""

import jax
import jax.numpy as jnp

BN_EPS = 1e-5
PRECISION = "highest"
STAGES = (3, 4, 6, 3)


def _conv(x, w, stride=1, padding="SAME"):
    return jax.lax.conv_general_dilated(
        x, w.astype(jnp.float32), (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _bn(x, p):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    return (x - mean) * jax.lax.rsqrt(var + BN_EPS) * p["scale"] + p["bias"]


def _bottleneck(p, x, stride):
    y = jax.nn.relu(_bn(_conv(x, p["Conv_0"]["kernel"]), p["BatchNorm_0"]))
    y = jax.nn.relu(_bn(_conv(y, p["Conv_1"]["kernel"], stride),
                        p["BatchNorm_1"]))
    y = _bn(_conv(y, p["Conv_2"]["kernel"]), p["BatchNorm_2"])
    if "conv_proj" in p:
        x = _bn(_conv(x, p["conv_proj"]["kernel"], stride), p["norm_proj"])
    return jax.nn.relu(x + y)


def logits(params, images):
    with jax.default_matmul_precision(PRECISION):
        x = images.astype(jnp.float32)
        x = _conv(x, params["conv_init"]["kernel"], 2, [(3, 3), (3, 3)])
        x = jax.nn.relu(_bn(x, params["bn_init"]))
        x = jax.lax.reduce_window(
            x, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
            ((0, 0), (1, 1), (1, 1), (0, 0)))
        block = 0
        for stage, count in enumerate(STAGES):
            for j in range(count):
                stride = 2 if stage > 0 and j == 0 else 1
                x = _bottleneck(params[f"BottleneckBlock_{block}"], x, stride)
                block += 1
        x = jnp.mean(x, axis=(1, 2))
        d = params["Dense_0"]
        return x @ d["kernel"].astype(jnp.float32) + d["bias"]


@jax.jit
def loss(params, images, labels):
    """Mean softmax cross-entropy of the batch, training mode."""
    logp = jax.nn.log_softmax(logits(params, images), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))
