"""ResNet family (ResNet-18/50), TPU-first.

Parity role: the reference's Data baseline runs torch ResNet-50 batch
inference inside `map_batches` actor pools (BASELINE.json configs,
SURVEY.md §6) and Train's MNIST/ResNet examples. Here the model is
native: NHWC layout (XLA-TPU's preferred conv layout), bf16 convs on the
MXU, fp32 batch-norm statistics, and a jit-friendly inference entry that
`data.Dataset.map_batches` actor pools call per batch.

Plain dict pytrees like the other model families; `resnet_param_axes`
gives logical axes so the same partition rule tables apply (convs shard
on the output-channel axis for TP).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class ResNetConfig:
    # stage_sizes/bottleneck pick the variant: [2,2,2,2]+False = ResNet-18,
    # [3,4,6,3]+True = ResNet-50.
    stage_sizes: Tuple[int, ...] = (3, 4, 6, 3)
    bottleneck: bool = True
    num_classes: int = 1000
    width: int = 64
    dtype: Any = jnp.bfloat16

    @classmethod
    def resnet50(cls) -> "ResNetConfig":
        return cls(stage_sizes=(3, 4, 6, 3), bottleneck=True)

    @classmethod
    def resnet18(cls) -> "ResNetConfig":
        return cls(stage_sizes=(2, 2, 2, 2), bottleneck=False)

    @classmethod
    def tiny(cls) -> "ResNetConfig":
        """Small variant for CPU tests."""
        return cls(stage_sizes=(1, 1), bottleneck=False, num_classes=10,
                   width=8)


def _conv_init(key, kh, kw, cin, cout, dtype):
    fan_in = kh * kw * cin
    w = jax.random.normal(key, (kh, kw, cin, cout)) * (2.0 / fan_in) ** 0.5
    return w.astype(dtype)


def _bn_init(c):
    return {"scale": jnp.ones((c,), jnp.float32),
            "bias": jnp.zeros((c,), jnp.float32),
            "mean": jnp.zeros((c,), jnp.float32),
            "var": jnp.ones((c,), jnp.float32)}


def _block_channels(cfg: ResNetConfig, stage: int) -> Tuple[int, int]:
    """(inner, out) channels of a block in `stage`."""
    inner = cfg.width * (2 ** stage)
    out = inner * 4 if cfg.bottleneck else inner
    return inner, out


def resnet_init(key, cfg: ResNetConfig) -> Dict:
    keys = iter(jax.random.split(key, 256))
    params: Dict[str, Any] = {
        "stem": {"conv": _conv_init(next(keys), 7, 7, 3, cfg.width,
                                    cfg.dtype),
                 "bn": _bn_init(cfg.width)},
        "stages": [],
    }
    cin = cfg.width
    for stage, n_blocks in enumerate(cfg.stage_sizes):
        inner, cout = _block_channels(cfg, stage)
        blocks: List[Dict] = []
        for b in range(n_blocks):
            blk: Dict[str, Any] = {}
            if cfg.bottleneck:
                blk["conv1"] = _conv_init(next(keys), 1, 1, cin, inner,
                                          cfg.dtype)
                blk["bn1"] = _bn_init(inner)
                blk["conv2"] = _conv_init(next(keys), 3, 3, inner, inner,
                                          cfg.dtype)
                blk["bn2"] = _bn_init(inner)
                blk["conv3"] = _conv_init(next(keys), 1, 1, inner, cout,
                                          cfg.dtype)
                blk["bn3"] = _bn_init(cout)
            else:
                blk["conv1"] = _conv_init(next(keys), 3, 3, cin, inner,
                                          cfg.dtype)
                blk["bn1"] = _bn_init(inner)
                blk["conv2"] = _conv_init(next(keys), 3, 3, inner, cout,
                                          cfg.dtype)
                blk["bn2"] = _bn_init(cout)
            if b == 0 and (cin != cout or stage > 0):
                blk["proj"] = _conv_init(next(keys), 1, 1, cin, cout,
                                         cfg.dtype)
                blk["proj_bn"] = _bn_init(cout)
            blocks.append(blk)
            cin = cout
        params["stages"].append(blocks)
    k = next(keys)
    params["head"] = {
        "w": (jax.random.normal(k, (cin, cfg.num_classes))
              * cin ** -0.5).astype(cfg.dtype),
        "b": jnp.zeros((cfg.num_classes,), jnp.float32),
    }
    return params


def resnet_param_axes(cfg: ResNetConfig) -> Dict:
    """Logical axes: convs shard output channels (-> 'mlp' axis for TP)."""
    conv = (None, None, None, "mlp")
    bn = {"scale": ("mlp",), "bias": ("mlp",),
          "mean": ("mlp",), "var": ("mlp",)}
    axes: Dict[str, Any] = {
        "stem": {"conv": conv, "bn": dict(bn)},
        "stages": [],
        "head": {"w": ("embed", "vocab"), "b": ("vocab",)},
    }
    cin = cfg.width
    for stage, n_blocks in enumerate(cfg.stage_sizes):
        _, cout = _block_channels(cfg, stage)
        blocks = []
        for b in range(n_blocks):
            blk: Dict[str, Any] = {"conv1": conv, "bn1": dict(bn),
                                   "conv2": conv, "bn2": dict(bn)}
            if cfg.bottleneck:
                blk["conv3"] = conv
                blk["bn3"] = dict(bn)
            if b == 0 and (cin != cout or stage > 0):
                blk["proj"] = conv
                blk["proj_bn"] = dict(bn)
            blocks.append(blk)
            cin = cout
        axes["stages"].append(blocks)
    return axes


def _conv(x, w, stride=1):
    return jax.lax.conv_general_dilated(
        x, w, window_strides=(stride, stride), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=jnp.float32).astype(x.dtype)


def _bn(x, p, eps=1e-5):
    """Inference batch-norm with stored statistics (fp32 math)."""
    xf = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(p["var"] + eps) * p["scale"]
    return (xf * inv + (p["bias"] - p["mean"] * inv)).astype(x.dtype)


def _residual_block(x, blk, cfg: ResNetConfig, stride: int):
    shortcut = x
    if cfg.bottleneck:
        y = jax.nn.relu(_bn(_conv(x, blk["conv1"]), blk["bn1"]))
        y = jax.nn.relu(_bn(_conv(y, blk["conv2"], stride), blk["bn2"]))
        y = _bn(_conv(y, blk["conv3"]), blk["bn3"])
    else:
        y = jax.nn.relu(_bn(_conv(x, blk["conv1"], stride), blk["bn1"]))
        y = _bn(_conv(y, blk["conv2"]), blk["bn2"])
    if "proj" in blk:
        shortcut = _bn(_conv(x, blk["proj"], stride), blk["proj_bn"])
    return jax.nn.relu(y + shortcut)


def resnet_forward(params: Dict, images, cfg: ResNetConfig):
    """images [batch, h, w, 3] float -> logits [batch, classes] fp32."""
    x = images.astype(cfg.dtype)
    x = jax.nn.relu(_bn(_conv(x, params["stem"]["conv"], 2),
                        params["stem"]["bn"]))
    x = jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1), "SAME")
    for stage, blocks in enumerate(params["stages"]):
        for b, blk in enumerate(blocks):
            stride = 2 if (stage > 0 and b == 0) else 1
            x = _residual_block(x, blk, cfg, stride)
    x = jnp.mean(x.astype(jnp.float32), axis=(1, 2))  # global avg pool
    head = params["head"]
    return x @ head["w"].astype(jnp.float32) + head["b"]


def make_predictor(cfg: ResNetConfig, params=None, key=None):
    """Jitted batch-inference callable for Data actor pools
    (reference pattern: map_batches(predictor_cls, num_gpus=1) —
    data/_internal/execution/operators/actor_pool_map_operator.py:34).

    Host inputs are explicitly device_put before the jitted call: the
    explicit put overlaps with the previous batch's compute under jax's
    async dispatch."""
    if params is None:
        if key is None:
            key = jax.random.PRNGKey(0)
        params = resnet_init(key, cfg)

    @jax.jit
    def _predict(images):
        return jnp.argmax(resnet_forward(params, images, cfg), axis=-1)

    def predict(images):
        if not isinstance(images, jax.Array):
            images = jax.device_put(images)
        return _predict(images)

    return predict
