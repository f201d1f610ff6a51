"""Family ``image_classifier``: ImageNet ResNets (He et al.,
arXiv:1512.03385; bottleneck blocks, stride on the 3x3 as torchvision has
it) through ``models.resnet_imagenet`` behind ``trainer.SGD``, with the
yardstick's own FLOPs count and a plain float32 reference.

The reference shares the program's initial weights by walking them in
the order the program created them: in a block, the projection shortcut
(where there is one) before the three convolutions, each convolution
followed by its batch-norm scale and bias; the classifier last.
"""
from __future__ import annotations

from typing import Dict, Iterator, List

import numpy as np

from benchmark.harness import TrainProgram

ITEM = "img"
_BLOCKS = {50: (3, 4, 6, 3), 101: (3, 4, 23, 3), 152: (3, 8, 36, 3)}
_WIDTHS = (64, 128, 256, 512)
_BN_EPS = 1e-5


# ---------------------------------------------------------------------------
# the program under test
# ---------------------------------------------------------------------------
def build_train(config: dict, mix: dict, seed: int, plan=None) -> TrainProgram:
    import paddle_tpu as pt
    from paddle_tpu import layers, models

    hw = config["image_size"]
    scope = pt.Scope()
    main, startup = pt.Program(), pt.Program()
    main.random_seed = startup.random_seed = seed
    with pt.program_guard(main, startup):
        images = layers.data("images", shape=[hw, hw, 3])
        label = layers.data("label", shape=[1], dtype="int64")
        logits = models.resnet_imagenet(
            images, num_classes=config["num_classes"],
            depth=config["depth"])
        loss = layers.mean(layers.softmax_with_cross_entropy(logits, label))
        opt = mix["optimizer"]
        if opt["name"] != "momentum":
            raise ValueError(f"image_classifier trains with momentum, "
                             f"not {opt}")
        sgd = pt.trainer.SGD(
            loss, pt.optimizer.MomentumOptimizer(
                learning_rate=opt["lr"], momentum=opt["momentum"]),
            [images, label], scope=scope, plan=plan)
    return TrainProgram(sgd=sgd, scope=scope, main=main)


def batches(config: dict, mix: dict, seed: int) -> Iterator[list]:
    """Endless cycle over a pool of ``pool`` seeded host batches: the
    host-to-device feed is paid every step, the random numbers once."""
    rng = np.random.RandomState(seed)
    hw, B = config["image_size"], mix["batch"]
    pool = []
    for _ in range(mix["pool"]):
        img = rng.random_sample((B, hw, hw, 3)).astype(np.float32)
        lab = rng.randint(0, config["num_classes"],
                          size=(B, 1)).astype(np.int64)
        pool.append([(img[i], lab[i]) for i in range(B)])
    while True:
        yield from pool


def items_per_step(mix: dict) -> int:
    return mix["batch"]


def _convs(config: dict):
    """(out_hw, kernel, c_in, c_out) of every convolution, then the
    classifier as a 1x1 on a 1x1 map."""
    hw = config["image_size"] // 2
    yield hw, 7, 3, 64
    hw //= 2                                   # 3x3/2 max pool
    c_in = 64
    for stage, (mid, n) in enumerate(zip(_WIDTHS, _BLOCKS[config["depth"]])):
        for block in range(n):
            stride = 2 if block == 0 and stage > 0 else 1
            out_hw = hw // stride
            if c_in != mid * 4 or stride != 1:
                yield out_hw, 1, c_in, mid * 4          # projection
            yield hw, 1, c_in, mid
            yield out_hw, 3, mid, mid
            yield out_hw, 1, mid, mid * 4
            hw, c_in = out_hw, mid * 4
    yield 1, 1, c_in, config["num_classes"]


def flops_per_item(config: dict, mix: dict) -> float:
    """Model FLOPs per trained image: the multiply-adds of every
    convolution and of the classifier, 2 FLOPs each, forward + backward
    (3x forward). Norms, activations and pooling are not counted."""
    macs = sum(hw * hw * k * k * ci * co for hw, k, ci, co in _convs(config))
    return 3.0 * 2.0 * macs


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------
def weights_of(program, scope) -> List[object]:
    """The parameters in creation order (see the module docstring)."""
    return [scope.get(p.name) for p in program.all_parameters()]


def reference_loss(config: dict, params: List[object],
                   feed: Dict[str, np.ndarray]) -> float:
    """Softmax cross entropy of the batch under TRAIN-mode batch
    statistics, plain ``lax.conv_general_dilated`` in float32."""
    import jax
    import jax.numpy as jnp

    def forward(params, images, labels):
        it = iter(params)

        def conv_bn(x, stride, relu):
            w = next(it)                               # HWIO
            pad = (w.shape[0] - 1) // 2
            x = jax.lax.conv_general_dilated(
                x, w, (stride, stride), [(pad, pad), (pad, pad)],
                dimension_numbers=("NHWC", "HWIO", "NHWC"))
            scale, bias = next(it), next(it)
            mean = jnp.mean(x, axis=(0, 1, 2))
            var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
            x = (x - mean) / jnp.sqrt(var + _BN_EPS) * scale + bias
            return jnp.maximum(x, 0.0) if relu else x

        x = conv_bn(images, 2, True)
        x = jax.lax.reduce_window(
            x, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
            [(0, 0), (1, 1), (1, 1), (0, 0)])
        for stage, (mid, n) in enumerate(
                zip(_WIDTHS, _BLOCKS[config["depth"]])):
            for block in range(n):
                stride = 2 if block == 0 and stage > 0 else 1
                short = x
                if x.shape[-1] != mid * 4 or stride != 1:
                    short = conv_bn(x, stride, False)
                y = conv_bn(x, 1, True)
                y = conv_bn(y, stride, True)
                y = conv_bn(y, 1, False)
                x = jnp.maximum(y + short, 0.0)
        x = jnp.mean(x, axis=(1, 2))
        logits = x @ next(it) + next(it)
        if next(it, None) is not None:
            raise ValueError("the program has more parameters than the "
                             "reference's architecture consumes")
        lse = jax.nn.logsumexp(logits, axis=-1)
        tok = jnp.take_along_axis(logits, labels, axis=-1)[:, 0]
        return jnp.mean(lse - tok)

    with jax.default_matmul_precision("highest"):
        return float(jax.jit(forward)(
            list(params), jnp.asarray(feed["images"], jnp.float32),
            jnp.asarray(feed["label"], jnp.int32)))
