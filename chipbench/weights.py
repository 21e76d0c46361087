"""The benchmark's own inputs: weights, batch-norm statistics and images,
all made from ``--seed``. Nothing here imports the program.

The weights are built in the layout the served model takes (the parameter
tree that the configuration's architecture module gives, ``shapes(cfg)``),
on the device, in one jitted call. Images are made on the host with numpy,
once, before the measured window opens.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

#: Calibration batches and request pools draw from separate streams.
_CALIB_STREAM, _POOL_STREAM = 1, 2


def param_count(arch, cfg: dict) -> int:
    params, _ = arch.shapes(cfg)
    leaves = jax.tree.leaves(params, is_leaf=lambda x: isinstance(x, tuple))
    return sum(int(np.prod(s)) for s in leaves)


def _leaf(key, path: str, shape: tuple):
    """One leaf, by its name: He-normal convolutions (every 4-D leaf), a
    fan-in-scaled ``head`` and zero ``head_b``, and batch norms whose
    ``scale``, ``bias`` and running ``mean`` and ``var`` all differ from the
    identity, so that the served network cannot skip them unseen. An
    architecture module names its leaves from these."""
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


def make_weights(arch, cfg: dict, seed: int):
    """``(params, state)`` on the device, fp32, from ``seed``; one key per
    leaf, split in the order of the flattened shape tree."""
    words = _key(seed, 0).generate_state(2)
    key = jax.random.fold_in(jax.random.PRNGKey(int(words[0])),
                             int(words[1]))
    params, state = arch.shapes(cfg)
    out = jax.jit(lambda k: _make(k, {"params": params, "state": state}))(key)
    out = jax.block_until_ready(out)
    return out["params"], out["state"]


def images(cfg: dict, seed: int, n: int, stream: int = _POOL_STREAM
           ) -> np.ndarray:
    """``n`` images ``(n, H, W, C)`` fp32 on the host: noise plus a class
    tint on one channel, from 0.3 up to 1.3 whatever the class count (the
    synthetic CIFAR recipe of ``repro.data.pipeline.cifar_batch_at``, made
    with numpy)."""
    rng = np.random.default_rng(_key(seed, stream))
    h, w, c = cfg["image_shape"]
    labels = rng.integers(0, cfg["num_classes"], n)
    x = rng.standard_normal((n, h, w, c), dtype=np.float32) * 0.3
    tint = np.zeros((n, c), np.float32)
    tint[np.arange(n), labels % c] = labels / cfg["num_classes"] + 0.3
    return x + tint[:, None, None, :]


def calibration_images(cfg: dict, seed: int) -> list[np.ndarray]:
    cal = cfg["calibration"]
    x = images(cfg, seed, cal["batches"] * cal["batch"], _CALIB_STREAM)
    return np.split(x, cal["batches"])
