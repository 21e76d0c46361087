"""ResNet in CIFAR geometry (arXiv:2004.11077, section 5): a 3x3 stride-1
stem, no max-pool, basic blocks whose first block in every stage after the
first halves the image (a stride-2 3x3 convolution and a 1x1 projection),
global average pooling and a linear head.

What the benchmark knows of this network: the shapes of its weights, the
plain reference forward, its convolutions for the operation count, and the
program's model and config that serve it. The configuration states
``widths`` (one per stage) and ``blocks_per_stage`` besides the common
keys (``chipbench.harness.COMMON_KEYS``).
"""
import jax
import jax.numpy as jnp

from chipbench.reference import bn, conv

KEYS = ("widths", "blocks_per_stage")


def blocks(cfg: dict):
    """Yield ``(name, cin, cout, stride)`` of every basic block, in order."""
    widths = cfg["widths"]
    cin = widths[0]
    for si, (n, cout) in enumerate(zip(cfg["blocks_per_stage"], widths)):
        for bi in range(n):
            stride = 2 if (si > 0 and bi == 0) else 1
            yield f"s{si}b{bi}", cin, cout, stride
            cin = cout


def shapes(cfg: dict):
    """Parameter and statistic shapes, as nested dicts of tuples."""
    w0, cin_img = cfg["widths"][0], cfg["image_shape"][-1]
    affine = lambda c: {"scale": (c,), "bias": (c,)}
    stats = lambda c: {"mean": (c,), "var": (c,)}
    params = {"stem": (3, 3, cin_img, w0), "bn_stem": affine(w0),
              "head": (cfg["widths"][-1], cfg["num_classes"]),
              "head_b": (cfg["num_classes"],), "blocks": {}}
    state = {"bn_stem": stats(w0), "blocks": {}}
    for name, cin, cout, stride in blocks(cfg):
        p = {"conv1": (3, 3, cin, cout), "bn1": affine(cout),
             "conv2": (3, 3, cout, cout), "bn2": affine(cout)}
        s = {"bn1": stats(cout), "bn2": stats(cout)}
        if stride != 1 or cin != cout:
            p["proj"], p["bn_proj"] = (1, 1, cin, cout), affine(cout)
            s["bn_proj"] = stats(cout)
        params["blocks"][name], state["blocks"][name] = p, s
    return params, state


def conv_layers(cfg: dict):
    """``(name, H_out, W_out, k, Cin, Cout, stride)`` of every convolution."""
    h, w, c = cfg["image_shape"]
    yield "stem", h, w, 3, c, cfg["widths"][0], 1
    for name, cin, cout, stride in blocks(cfg):
        ho, wo = -(-h // stride), -(-w // stride)
        yield f"{name}.conv1", ho, wo, 3, cin, cout, stride
        yield f"{name}.conv2", ho, wo, 3, cout, cout, 1
        if stride != 1 or cin != cout:
            yield f"{name}.proj", ho, wo, 1, cin, cout, stride
        h, w = ho, wo


def forward(cfg: dict, params, state, x, bits=None):
    """images ``(B, H, W, C)`` → logits ``(B, classes)``."""
    x = jax.nn.relu(bn(conv(x, params["stem"], 1, bits),
                       params["bn_stem"], state["bn_stem"]))
    for name, _, _, stride in blocks(cfg):
        p, s = params["blocks"][name], state["blocks"][name]
        h = jax.nn.relu(bn(conv(x, p["conv1"], stride, bits),
                           p["bn1"], s["bn1"]))
        h = bn(conv(h, p["conv2"], 1, bits), p["bn2"], s["bn2"])
        if "proj" in p:
            x = bn(conv(x, p["proj"], stride, bits), p["bn_proj"],
                   s["bn_proj"])
        x = jax.nn.relu(h + x)
    x = jnp.mean(x, axis=(1, 2))
    return jnp.dot(x, params["head"], precision=jax.lax.Precision.HIGHEST) \
        + params["head_b"]


def program(cfg: dict):
    """The program's model module (``repro.models.resnet``) and its config
    for this configuration. The program fixes what the configuration
    states besides widths, classes and the Winograd spec (its blocks per
    stage, stem and input channels), so its parameter and statistic trees
    are checked against ``shapes(cfg)``: a file that states a network the
    program does not build is refused, not served as another."""
    from repro.core.quantization import QuantConfig
    from repro.core.winograd import WinogradSpec
    from repro.models import resnet as RN
    from repro.models.param import ParamSpec

    wino, bits = cfg["winograd"], cfg["bits"]
    rc = RN.ResNetConfig(
        width_mult=cfg["widths"][0] / 64,
        wino=WinogradSpec(m=wino["m"], r=wino["r"], base=wino["base"],
                          quant=QuantConfig(act_bits=bits["act"],
                                            weight_bits=bits["weight"],
                                            trans_bits=bits["transform"],
                                            hadamard_bits=bits["hadamard"])),
        num_classes=cfg["num_classes"])
    built = jax.tree.map(lambda p: tuple(p.shape),
                         (RN.param_specs(rc), RN.state_specs(rc)),
                         is_leaf=lambda x: isinstance(x, ParamSpec))
    if built != shapes(cfg):
        raise ValueError(
            f"the program builds widths {list(rc.widths)}, blocks "
            f"{sorted(built[0]['blocks'])} and stem {built[0]['stem']}; "
            f"the configuration states widths {cfg['widths']}, blocks per "
            f"stage {cfg['blocks_per_stage']} and {cfg['image_shape']} "
            "images")
    return RN, rc
