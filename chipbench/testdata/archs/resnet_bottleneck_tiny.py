"""A test-only architecture: a bottleneck ResNet in its ImageNet form (He
et al., arXiv:1512.03385; ResNet-50 v1.5, with the stride on the 3x3
convolution), for any widths. A 7x7 stride-2 stem, a 3x3 stride-2
max-pool, bottleneck blocks (1x1 down to the stage width, 3x3, 1x1 up to
four times it, with a 1x1 projection where the shape changes), global
average pooling and a linear head.

Reference only: the program has no such model, so ``program`` raises. It
shows that a network comes into the benchmark by new files alone: this
module, a configuration that names it, and a traffic mix.
"""
import jax
import jax.numpy as jnp

from chipbench.reference import bn, conv

KEYS = ("widths", "blocks_per_stage")
EXPANSION = 4


def blocks(cfg: dict):
    """Yield ``(name, cin, width, stride)`` of every bottleneck block; it
    puts out ``EXPANSION * width`` channels."""
    cin = cfg["widths"][0]
    for si, (n, width) in enumerate(zip(cfg["blocks_per_stage"],
                                        cfg["widths"])):
        for bi in range(n):
            yield f"s{si}b{bi}", cin, width, 2 if (si > 0 and bi == 0) else 1
            cin = EXPANSION * width


def shapes(cfg: dict):
    """Parameter and statistic shapes, as nested dicts of tuples."""
    w0, cin_img = cfg["widths"][0], cfg["image_shape"][-1]
    affine = lambda c: {"scale": (c,), "bias": (c,)}
    stats = lambda c: {"mean": (c,), "var": (c,)}
    params = {"stem": (7, 7, cin_img, w0), "bn_stem": affine(w0),
              "head": (EXPANSION * cfg["widths"][-1], cfg["num_classes"]),
              "head_b": (cfg["num_classes"],), "blocks": {}}
    state = {"bn_stem": stats(w0), "blocks": {}}
    for name, cin, width, stride in blocks(cfg):
        cout = EXPANSION * width
        p = {"conv1": (1, 1, cin, width), "bn1": affine(width),
             "conv2": (3, 3, width, width), "bn2": affine(width),
             "conv3": (1, 1, width, cout), "bn3": affine(cout)}
        s = {"bn1": stats(width), "bn2": stats(width), "bn3": stats(cout)}
        if stride != 1 or cin != cout:
            p["proj"], p["bn_proj"] = (1, 1, cin, cout), affine(cout)
            s["bn_proj"] = stats(cout)
        params["blocks"][name], state["blocks"][name] = p, s
    return params, state


def conv_layers(cfg: dict):
    """``(name, H_out, W_out, k, Cin, Cout, stride)`` of every convolution."""
    h, w, c = cfg["image_shape"]
    h, w = -(-h // 2), -(-w // 2)
    yield "stem", h, w, 7, c, cfg["widths"][0], 2
    h, w = -(-h // 2), -(-w // 2)                 # the max-pool
    for name, cin, width, stride in blocks(cfg):
        ho, wo = -(-h // stride), -(-w // stride)
        yield f"{name}.conv1", h, w, 1, cin, width, 1
        yield f"{name}.conv2", ho, wo, 3, width, width, stride
        yield f"{name}.conv3", ho, wo, 1, width, EXPANSION * width, 1
        if stride != 1 or cin != EXPANSION * width:
            yield f"{name}.proj", ho, wo, 1, cin, EXPANSION * width, stride
        h, w = ho, wo


def forward(cfg: dict, params, state, x, bits=None):
    """images ``(B, H, W, C)`` → logits ``(B, classes)``."""
    x = jax.lax.conv_general_dilated(          # the 7x7/2 stem, padding 3
        x, params["stem"], (2, 2), ((3, 3), (3, 3)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST)
    x = jax.nn.relu(bn(x, params["bn_stem"], state["bn_stem"]))
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), ((0, 0), (1, 1), (1, 1), (0, 0)))
    for name, _, _, stride in blocks(cfg):
        p, s = params["blocks"][name], state["blocks"][name]
        h = jax.nn.relu(bn(conv(x, p["conv1"], 1, bits), p["bn1"], s["bn1"]))
        h = jax.nn.relu(bn(conv(h, p["conv2"], stride, bits), p["bn2"],
                           s["bn2"]))
        h = bn(conv(h, p["conv3"], 1, bits), p["bn3"], s["bn3"])
        if "proj" in p:
            x = bn(conv(x, p["proj"], stride, bits), p["bn_proj"],
                   s["bn_proj"])
        x = jax.nn.relu(h + x)
    x = jnp.mean(x, axis=(1, 2))
    return jnp.dot(x, params["head"], precision=jax.lax.Precision.HIGHEST) \
        + params["head_b"]


def program(cfg: dict):
    raise NotImplementedError("the program serves no bottleneck ResNet")
