"""The plain reference: inference in ``jax.numpy`` and float32.

The network itself is the architecture module's ``forward`` (``archs/``),
built from the operations here: direct convolutions at ``highest`` matmul
precision (on a TPU a float32 matmul otherwise runs in one bf16 pass) and
batch norm from running statistics. No Winograd transform, no
quantisation, no kernels, no batching logic. It imports nothing of the
program and takes only what the benchmark made (``weights.py``).

``bits=4`` gives the control: the same network with the inputs and weights
of every stride-1 3x3 convolution, the layers the program serves in int8,
fake-quantised to a symmetric 4-bit grid (per image, per output channel).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def fq(x, bits: int, axes):
    """Symmetric fake quantisation, one scale per slice kept by ``axes``."""
    qm = 2 ** (bits - 1) - 1
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axes, keepdims=True), 1e-30)
    return jnp.clip(jnp.round(x / s * qm), -qm, qm) * s / qm


def conv(x, w, stride: int, bits):
    """A direct NHWC convolution; with ``bits``, a stride-1 3x3 one on
    fake-quantised inputs and weights."""
    if bits is not None and stride == 1 and w.shape[0] == 3:
        x = fq(x, bits, (1, 2, 3))          # per image
        w = fq(w, bits, (0, 1, 2))          # per output channel
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST)


def bn(x, p, s):
    return (x - s["mean"]) * jax.lax.rsqrt(s["var"] + 1e-5) * p["scale"] \
        + p["bias"]


def logits(arch, cfg: dict, params, state, images: np.ndarray, bits=None,
           block: int = 256) -> np.ndarray:
    """The reference over ``images`` in blocks of rows, on the host."""
    fn = jax.jit(lambda x: arch.forward(cfg, params, state, x, bits))
    out = []
    for i in range(0, len(images), block):
        x = images[i:i + block]
        pad = np.zeros((block - len(x), *x.shape[1:]), x.dtype)
        y = np.asarray(fn(jnp.asarray(np.concatenate([x, pad]))))
        out.append(y[:len(x)])
    return np.concatenate(out).astype(np.float64)
