"""ResNet family (ray_tpu.models.resnet): config builder, operation
counts, and a plain float32 reference of inference with stored batch-norm
statistics (He et al. 2015, table 1; v1.5 placement of the stride on the
3x3 convolution, as the program has it). Shares no code with the program.
"""

from __future__ import annotations


def build(config: dict, **overrides):
    from ray_tpu.models import ResNetConfig

    kw = dict(stage_sizes=tuple(config["stage_sizes"]),
              bottleneck=config["bottleneck"],
              num_classes=config["num_classes"], width=config["width"])
    kw.update(overrides)
    return ResNetConfig(**kw)


def _convs(cfg, side: int):
    """(kernel, cin, cout, output side) of every convolution, in order."""
    side = -(-side // 2)
    yield 7, 3, cfg.width, side
    side = -(-side // 2)                      # 3x3 max pool, stride 2
    cin = cfg.width
    for stage, n_blocks in enumerate(cfg.stage_sizes):
        inner = cfg.width * 2 ** stage
        cout = inner * 4 if cfg.bottleneck else inner
        for b in range(n_blocks):
            stride = 2 if stage > 0 and b == 0 else 1
            in_side, side = side, -(-side // stride)
            if cfg.bottleneck:
                yield 1, cin, inner, in_side
                yield 3, inner, inner, side
                yield 1, inner, cout, side
            else:
                yield 3, cin, inner, side
                yield 3, inner, cout, side
            if b == 0 and (cin != cout or stage > 0):
                yield 1, cin, cout, side
            cin = cout


def forward_flops_per_image(cfg, side: int = 224) -> float:
    """Multiply-adds x 2 of every convolution and the classifier."""
    flops = sum(2.0 * k * k * cin * cout * s * s
                for k, cin, cout, s in _convs(cfg, side))
    last = cfg.width * 2 ** (len(cfg.stage_sizes) - 1) \
        * (4 if cfg.bottleneck else 1)
    return flops + 2.0 * last * cfg.num_classes


def input_bytes_per_image(side: int = 224) -> int:
    """float32 HWC, as the upstream transform yields it."""
    return side * side * 3 * 4


# ---------------------------------------------------------------------------
# plain float32 reference
# ---------------------------------------------------------------------------
def _conv(x, w, stride=1):
    import jax
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST)


def _bn(x, p, eps=1e-5):
    import jax.numpy as jnp
    return (x - p["mean"]) / jnp.sqrt(p["var"] + eps) * p["scale"] \
        + p["bias"]


def reference_logits(params, images, bottleneck: bool):
    """images [b, h, w, 3] float32 -> logits [b, classes] float32."""
    import jax
    import jax.numpy as jnp

    p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    relu = jax.nn.relu
    x = relu(_bn(_conv(images.astype(jnp.float32), p["stem"]["conv"], 2),
                 p["stem"]["bn"]))
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), "SAME")
    for stage, blocks in enumerate(p["stages"]):
        for b, blk in enumerate(blocks):
            stride = 2 if stage > 0 and b == 0 else 1
            if bottleneck:
                y = relu(_bn(_conv(x, blk["conv1"]), blk["bn1"]))
                y = relu(_bn(_conv(y, blk["conv2"], stride), blk["bn2"]))
                y = _bn(_conv(y, blk["conv3"]), blk["bn3"])
            else:
                y = relu(_bn(_conv(x, blk["conv1"], stride), blk["bn1"]))
                y = _bn(_conv(y, blk["conv2"]), blk["bn2"])
            short = x
            if "proj" in blk:
                short = _bn(_conv(x, blk["proj"], stride), blk["proj_bn"])
            x = relu(y + short)
    x = jnp.mean(x, axis=(1, 2))
    return jnp.dot(x, p["head"]["w"],
                   precision=jax.lax.Precision.HIGHEST) + p["head"]["b"]
