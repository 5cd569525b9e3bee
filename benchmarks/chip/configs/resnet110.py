"""ResNet-110 on CIFAR-shaped images: how the program builds it, the
weights the benchmark gives it, its plain reference and its FLOPs.

The reference is written from He et al. (2016), section 4.2, in float32 at
the highest matmul precision, and imports nothing of the program.  It keeps
two choices of the program, which are choices of the model and not of its
speed: group norm (8 groups) where the paper has batch norm, so the model
holds no running statistics, and option-A shortcuts (stride, then zero
channels).
"""
from __future__ import annotations

INPUT = "images"


def _n(cfg) -> int:
    return (cfg["depth"] - 2) // 6


def program(cfg):
    """The program's model and optimizer for this configuration."""
    import dataclasses

    from repro.configs.resnet110 import CONFIG
    from repro.models.resnet import ResNetModel
    from repro.optim.optimizers import sgd

    model_cfg = dataclasses.replace(
        CONFIG, depth=cfg["depth"], width=cfg["width"],
        num_classes=cfg["num_classes"], image_size=cfg["image_size"])
    opt = cfg["optimizer"]
    return ResNetModel(model_cfg), sgd(momentum=opt["momentum"],
                                       weight_decay=opt["weight_decay"])


def param_spec(cfg) -> dict:
    """Leaves ("normal", shape, std) with std 1/sqrt(fan-in), gains one,
    biases zero; stacked blocks carry a leading block axis."""
    n = _n(cfg)
    widths = [cfg["width"], 2 * cfg["width"], 4 * cfg["width"]]

    def conv(lead, cin, cout):
        return ("normal", (lead, 3, 3, cin, cout), (9 * cin) ** -0.5)

    def blocks(lead, cin, cout):
        return {"conv1": conv(lead, cin, cout), "n1s": ("ones", (lead, cout)),
                "n1b": ("zeros", (lead, cout)),
                "conv2": conv(lead, cout, cout), "n2s": ("ones", (lead, cout)),
                "n2b": ("zeros", (lead, cout))}

    p = {"stem": ("normal", (3, 3, 3, widths[0]), 27 ** -0.5),
         "stem_s": ("ones", (widths[0],)), "stem_b": ("zeros", (widths[0],))}
    cin = widths[0]
    for si, cout in enumerate(widths):
        p[f"stage{si}_first"] = blocks(1, cin, cout)
        if n > 1:
            p[f"stage{si}_rest"] = blocks(n - 1, cout, cout)
        cin = cout
    p["fc"] = ("normal", (widths[-1], cfg["num_classes"]), widths[-1] ** -0.5)
    p["fc_b"] = ("zeros", (cfg["num_classes"],))
    return p


def flops_per_sample(cfg) -> float:
    """Forward and backward FLOPs of one image: three forwards (the
    backward pass costs two)."""
    return 3 * forward_flops(cfg)


def _taps(n: int, stride: int, k: int = 3) -> int:
    """Kernel taps that land inside an n-wide input, summed over the output
    positions of a SAME convolution: products with the zero padding are no
    work the model needs."""
    out = -(-n // stride)
    lo = max((out - 1) * stride + k - n, 0) // 2
    return sum(1 for o in range(out) for t in range(k)
               if 0 <= o * stride - lo + t < n)


def forward_flops(cfg) -> float:
    """2 x multiply-adds of the convolutions and the classifier, from
    their shapes."""
    n, s = _n(cfg), cfg["image_size"]
    widths = [cfg["width"], 2 * cfg["width"], 4 * cfg["width"]]

    def conv(size, stride, cin, cout):
        return _taps(size, stride) ** 2 * cin * cout

    macs = conv(s, 1, 3, widths[0])
    cin = widths[0]
    for si, cout in enumerate(widths):
        stride = 1 if si == 0 else 2
        macs += conv(s, stride, cin, cout)
        s = -(-s // stride)
        macs += conv(s, 1, cout, cout) + (n - 1) * 2 * conv(s, 1, cout, cout)
        cin = cout
    macs += widths[-1] * cfg["num_classes"]
    return 2.0 * macs


# ------------------------------------------------------------ reference ---
def _forward(params, images, quant):
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST

    def conv(x, w, stride):
        return jax.lax.conv_general_dilated(
            quant(x), quant(w), (stride, stride), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=hi)

    def norm(x, scale, bias, groups=8, eps=1e-5):
        b, h, w, c = x.shape
        g = min(groups, c)
        xg = x.reshape(b, h, w, g, c // g)
        mu = xg.mean(axis=(1, 2, 4), keepdims=True)
        var = ((xg - mu) ** 2).mean(axis=(1, 2, 4), keepdims=True)
        return ((xg - mu) / jnp.sqrt(var + eps)).reshape(b, h, w, c) \
            * scale + bias

    def block(p, x, stride):
        h = jax.nn.relu(norm(conv(x, p["conv1"], stride), p["n1s"], p["n1b"]))
        h = norm(conv(h, p["conv2"], 1), p["n2s"], p["n2b"])
        if stride != 1 or x.shape[-1] != h.shape[-1]:
            x = x[:, ::stride, ::stride, :]
            x = jnp.pad(x, ((0, 0), (0, 0), (0, 0),
                            (0, h.shape[-1] - x.shape[-1])))
        return jax.nn.relu(x + h)

    x = jax.nn.relu(norm(conv(images, params["stem"], 1), params["stem_s"],
                         params["stem_b"]))
    for si in range(3):
        first = jax.tree_util.tree_map(lambda a: a[0],
                                       params[f"stage{si}_first"])
        x = block(first, x, 1 if si == 0 else 2)
        rest = params.get(f"stage{si}_rest")
        if rest is not None:
            x, _ = jax.lax.scan(
                jax.checkpoint(lambda x, p: (block(p, x, 1), None)), x, rest)
    x = x.mean(axis=(1, 2))
    return jnp.dot(quant(x), quant(params["fc"]), precision=hi) \
        + params["fc_b"]


def _block_loss_sum(params, images, labels, quant):
    import jax
    import jax.numpy as jnp

    logits = _forward(params, images, quant)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(lse - gold)


_GRAD = {}


def reference_grad(cfg, params, batch, quant, block_rows: int = 128):
    """Mean cross-entropy over the batch and its gradient, in blocks of
    rows so that any batch fits."""
    import jax
    import jax.numpy as jnp

    key = quant.__name__
    if key not in _GRAD:
        _GRAD[key] = jax.jit(jax.value_and_grad(
            lambda p, x, y: _block_loss_sum(p, x, y, quant)))
    fn = _GRAD[key]
    rows = batch["labels"].shape[0]
    total, grads = 0.0, None
    for i in range(0, rows, block_rows):
        s, g = fn(params, jnp.asarray(batch["images"][i:i + block_rows]),
                  jnp.asarray(batch["labels"][i:i + block_rows]))
        total += float(s)
        grads = g if grads is None else jax.tree_util.tree_map(
            jnp.add, grads, g)
    return total / rows, jax.tree_util.tree_map(lambda g: g / rows, grads)
