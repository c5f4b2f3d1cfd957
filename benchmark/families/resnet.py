"""ResNet-50 for ImageNet: how the program's model is built from the
configuration file, seeded data, the FLOP count, and the plain reference.

The reference is the published network (He et al., arXiv:1512.03385, table
1, 50-layer; stride on the 3x3 as torchvision has it) written out in
``jax.numpy`` at float32 and matmul precision "highest", in training mode
(batch statistics), reading the program's parameter tree and nothing else
of the program.  Departure from the paper: the 7x7/2 stem is held in its
space-to-depth form (4x4 kernel over 12 channels), as the configuration's
``assumed`` says, so the reference applies the same rearrangement."""

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import flops

STAGES = [(64, 3), (128, 4), (256, 6), (512, 3)]
BN_EPS = 1e-5


def build_model(cfg):
    from bigdl_tpu.models.resnet import resnet50

    return resnet50(classes=cfg["classes"], stem=cfg["stem"])


def make_train_data(cfg, traffic, seed):
    """Seeded float32 images in [0, 1) and int32 labels, one generator call
    each."""
    rng = np.random.default_rng(seed)
    n, hw = traffic.get("examples", cfg["train_examples"]), cfg["image_size"]
    x = rng.random((n, hw, hw, 3), dtype=np.float32)
    y = rng.integers(0, cfg["classes"], (n,), dtype=np.int32)
    return x, y


def train_flops_per_sample(cfg, traffic):
    return flops.TRAIN_OVER_FORWARD * flops.resnet50_forward_flops(
        cfg["image_size"], cfg["classes"])


# -- the plain reference -------------------------------------------------------

def _conv(x, w, stride=1, padding="SAME"):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _bn(x, p):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    return (x - mean) / jnp.sqrt(var + BN_EPS) * p["weight"] + p["bias"]


def _bottleneck(x, p, stride):
    b = p["body"]
    y = jnp.maximum(_bn(_conv(x, b["0_Conv2D"]["weight"]), b["1__BN"]), 0)
    y = jnp.maximum(_bn(_conv(y, b["3_Conv2D"]["weight"], stride),
                        b["4__BN"]), 0)
    y = _bn(_conv(y, b["6_Conv2D"]["weight"]), b["7__BN"])
    if "proj" in p:
        x = _bn(_conv(x, p["proj"]["0_Conv2D"]["weight"], stride),
                p["proj"]["1__BN"])
    return jnp.maximum(y + x, 0)


@jax.jit
def _forward_loss(params, x, y):
    n, h, w, c = x.shape
    x = x.reshape(n, h // 2, 2, w // 2, 2, c).transpose(
        0, 1, 3, 2, 4, 5).reshape(n, h // 2, w // 2, 4 * c)
    x = _conv(x, params["0_SpaceToDepthStem"]["weight"],
              padding=((1, 2), (1, 2)))
    x = jnp.maximum(_bn(x, params["1__BN"]), 0)
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), ((0, 0), (1, 1), (1, 1), (0, 0)))
    i = 4
    for stage, (_, blocks) in enumerate(STAGES):
        for b in range(blocks):
            x = _bottleneck(x, params[f"{i}_Bottleneck"],
                            2 if (stage > 0 and b == 0) else 1)
            i += 1
    x = jnp.mean(x, axis=(1, 2))
    head = params[f"{i + 1}_Linear"]
    logp = jax.nn.log_softmax(x @ head["weight"] + head["bias"])
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], 1))


def reference_loss(cfg, params, x, y):
    """Mean cross-entropy of the first training step's forward pass."""
    with jax.default_matmul_precision("highest"):
        return float(_forward_loss(
            jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32),
                                   params),
            jnp.asarray(x, jnp.float32), jnp.asarray(y, jnp.int32)))
