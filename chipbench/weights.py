"""The benchmark's own inputs: weights, batch-norm statistics and images,
all made from ``--seed``. Nothing here imports the program.

The weights are built in the layout the served model takes (the parameter
tree of a ResNet-18 as ``configs/<name>.json`` describes it), on the device,
in one jitted call. Images are made on the host with numpy, once, before
the measured window opens.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

#: Calibration batches and request pools draw from separate streams.
_CALIB_STREAM, _POOL_STREAM = 1, 2


def blocks(cfg: dict):
    """Yield ``(name, cin, cout, stride)`` of every basic block, in order."""
    widths = cfg["widths"]
    cin = widths[0]
    for si, (n, cout) in enumerate(zip(cfg["blocks_per_stage"], widths)):
        for bi in range(n):
            stride = 2 if (si > 0 and bi == 0) else 1
            yield f"s{si}b{bi}", cin, cout, stride
            cin = cout


def _shapes(cfg: dict):
    """Parameter and statistic shapes, as nested dicts of tuples."""
    w0, cin_img = cfg["widths"][0], cfg["image_shape"][-1]
    bn = lambda c: {"scale": (c,), "bias": (c,)}
    st = lambda c: {"mean": (c,), "var": (c,)}
    params = {"stem": (3, 3, cin_img, w0), "bn_stem": bn(w0),
              "head": (cfg["widths"][-1], cfg["num_classes"]),
              "head_b": (cfg["num_classes"],), "blocks": {}}
    state = {"bn_stem": st(w0), "blocks": {}}
    for name, cin, cout, stride in blocks(cfg):
        p = {"conv1": (3, 3, cin, cout), "bn1": bn(cout),
             "conv2": (3, 3, cout, cout), "bn2": bn(cout)}
        s = {"bn1": st(cout), "bn2": st(cout)}
        if stride != 1 or cin != cout:
            p["proj"], p["bn_proj"] = (1, 1, cin, cout), bn(cout)
            s["bn_proj"] = st(cout)
        params["blocks"][name], state["blocks"][name] = p, s
    return params, state


def param_count(cfg: dict) -> int:
    params, _ = _shapes(cfg)
    leaves = jax.tree.leaves(params, is_leaf=lambda x: isinstance(x, tuple))
    return sum(int(np.prod(s)) for s in leaves)


def _leaf(key, path: str, shape: tuple):
    """One leaf: He-normal convolutions, a fan-in-scaled head, and batch
    norms whose scale, shift and running statistics all differ from the
    identity, so that the served network cannot skip them unseen."""
    leaf = path.rsplit("/", 1)[-1]
    if len(shape) == 4:
        fan_in = shape[0] * shape[1] * shape[2]
        return jax.random.normal(key, shape) * np.sqrt(2.0 / fan_in)
    if leaf == "head":
        return jax.random.normal(key, shape) / np.sqrt(shape[0])
    if leaf == "scale":
        return 1.0 + 0.2 * jax.random.normal(key, shape)
    if leaf in ("bias", "mean"):
        return 0.1 * jax.random.normal(key, shape)
    if leaf == "var":
        return jax.random.uniform(key, shape, minval=0.5, maxval=1.5)
    if leaf == "head_b":
        return jnp.zeros(shape)
    raise ValueError(f"no initialiser for {path}")


def _key(seed: int, stream: int) -> np.random.SeedSequence:
    return np.random.SeedSequence([int(seed), stream])


def _make(key, shapes):
    flat, tree = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(key, len(flat))
    vals = [_leaf(k, jax.tree_util.keystr(p, simple=True, separator="/"),
                  s).astype(jnp.float32)
            for k, (p, s) in zip(keys, flat)]
    return jax.tree_util.tree_unflatten(tree, vals)


def make_weights(cfg: dict, seed: int):
    """``(params, state)`` on the device, fp32, from ``seed``."""
    words = _key(seed, 0).generate_state(2)
    key = jax.random.fold_in(jax.random.PRNGKey(int(words[0])),
                             int(words[1]))
    params, state = _shapes(cfg)
    out = jax.jit(lambda k: _make(k, {"params": params, "state": state}))(key)
    out = jax.block_until_ready(out)
    return out["params"], out["state"]


def images(cfg: dict, seed: int, n: int, stream: int = _POOL_STREAM
           ) -> np.ndarray:
    """``n`` CIFAR-like images ``(n, H, W, C)`` fp32 on the host: noise
    plus a class tint on one channel (the synthetic CIFAR recipe of
    ``repro.data.pipeline.cifar_batch_at``, made with numpy)."""
    rng = np.random.default_rng(_key(seed, stream))
    h, w, c = cfg["image_shape"]
    labels = rng.integers(0, cfg["num_classes"], n)
    x = rng.standard_normal((n, h, w, c), dtype=np.float32) * 0.3
    tint = np.zeros((n, c), np.float32)
    tint[np.arange(n), labels % c] = labels / 10.0 + 0.3
    return x + tint[:, None, None, :]


def calibration_images(cfg: dict, seed: int) -> list[np.ndarray]:
    cal = cfg["calibration"]
    x = images(cfg, seed, cal["batches"] * cal["batch"], _CALIB_STREAM)
    return np.split(x, cal["batches"])
