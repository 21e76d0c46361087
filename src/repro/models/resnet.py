"""ResNet18-CIFAR10 with Winograd-aware quantized convolutions — the
paper's own experimental model (channel multiplier 0.25 / 0.5 / 1.0).

Every convolution routes through ``repro.conv.ConvEngine``: the policy
sends stride-1 3×3 convs to the configured Winograd backend (fake-quant
QAT for training, true-int8 Pallas kernels for serving) and stride-2
convs / 1×1 shortcuts to direct convolution (outside the Winograd
regime), exactly the split in [5]'s reference code. ``make_engine``
builds the engine from a config; ``conv_layers`` enumerates the model's
convolutions for the engine's offline prepare/calibrate lifecycle (see
``repro.launch.infer_resnet`` for the full int8 serving flow).

BatchNorm keeps running statistics in a separate ``state`` pytree
(functional: train_step returns the updated state).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro import telemetry
from repro.conv import ConvEngine, ConvPolicy, LayerGeom
from repro.core.quantization import QuantConfig
from repro.core.winograd import WinogradSpec, flex_init
from repro.models.param import ParamSpec

__all__ = ["ResNetConfig", "param_specs", "state_specs", "forward",
           "loss_fn", "make_engine", "conv_layers", "layer_geoms",
           "serving_forward", "NUM_CLASSES"]

NUM_CLASSES = 10
_STAGES = (2, 2, 2, 2)          # ResNet18 basic blocks per stage
_WIDTHS = (64, 128, 256, 512)


@dataclasses.dataclass(frozen=True)
class ResNetConfig:
    name: str = "resnet18-cifar10"
    family: str = "cnn"
    width_mult: float = 0.5      # the paper's channel multiplier
    wino: Optional[WinogradSpec] = WinogradSpec(
        m=4, r=3, base="legendre", quant=QuantConfig())
    use_winograd: bool = True    # False → direct conv everywhere (baseline)
    conv_backend: Optional[str] = None   # engine backend for eligible convs
    # (None → "winograd_fakequant" when use_winograd else "direct")
    flex: bool = False           # learnable transform matrices
    num_classes: int = NUM_CLASSES
    param_dtype: str = "float32"
    bn_momentum: float = 0.9

    @property
    def dtype(self):
        return jnp.dtype(self.param_dtype)

    @property
    def widths(self):
        return tuple(max(8, int(w * self.width_mult)) for w in _WIDTHS)


def _conv_spec(cin, cout, k, cfg):
    return ParamSpec((k, k, cin, cout), (None, None, "embed", "mlp"),
                     scale=1.0, dtype=cfg.dtype)


def _bn_spec(c, cfg):
    return {"scale": ParamSpec((c,), (None,), init="ones", dtype=cfg.dtype),
            "bias": ParamSpec((c,), (None,), init="zeros", dtype=cfg.dtype)}


def _bn_state_spec(c, cfg):
    return {"mean": ParamSpec((c,), (None,), init="zeros",
                              dtype=jnp.float32),
            "var": ParamSpec((c,), (None,), init="ones", dtype=jnp.float32)}


def _block_specs(cin, cout, stride, cfg):
    s = {
        "conv1": _conv_spec(cin, cout, 3, cfg),
        "bn1": _bn_spec(cout, cfg),
        "conv2": _conv_spec(cout, cout, 3, cfg),
        "bn2": _bn_spec(cout, cfg),
    }
    if stride != 1 or cin != cout:
        s["proj"] = _conv_spec(cin, cout, 1, cfg)
        s["bn_proj"] = _bn_spec(cout, cfg)
    return s


def _block_state(cin, cout, stride, cfg):
    s = {"bn1": _bn_state_spec(cout, cfg), "bn2": _bn_state_spec(cout, cfg)}
    if stride != 1 or cin != cout:
        s["bn_proj"] = _bn_state_spec(cout, cfg)
    return s


def _iter_blocks(cfg):
    cin = cfg.widths[0]
    for si, (n, cout) in enumerate(zip(_STAGES, cfg.widths)):
        for bi in range(n):
            stride = 2 if (si > 0 and bi == 0) else 1
            yield f"s{si}b{bi}", cin, cout, stride
            cin = cout


def param_specs(cfg: ResNetConfig) -> dict:
    w0 = cfg.widths[0]
    specs = {
        "stem": _conv_spec(3, w0, 3, cfg),
        "bn_stem": _bn_spec(w0, cfg),
        "head": ParamSpec((cfg.widths[-1], cfg.num_classes),
                          ("embed", None), dtype=cfg.dtype),
        "head_b": ParamSpec((cfg.num_classes,), (None,), init="zeros",
                            dtype=cfg.dtype),
        "blocks": {nm: _block_specs(ci, co, st, cfg)
                   for nm, ci, co, st in _iter_blocks(cfg)},
    }
    if cfg.use_winograd and cfg.flex and cfg.wino is not None:
        fx = flex_init(cfg.wino)
        specs["wino_flex"] = {
            k: ParamSpec(tuple(v.shape), (None,) * v.ndim, init="zeros",
                         dtype=jnp.float32) for k, v in fx.items()}
    return specs


def state_specs(cfg: ResNetConfig) -> dict:
    w0 = cfg.widths[0]
    return {"bn_stem": _bn_state_spec(w0, cfg),
            "blocks": {nm: _block_state(ci, co, st, cfg)
                       for nm, ci, co, st in _iter_blocks(cfg)}}


def init_flex(cfg: ResNetConfig):
    """Proper flex init values (analytic matrices, not zeros)."""
    return flex_init(cfg.wino) if (cfg.use_winograd and cfg.flex) else None


def _bn(x, p, st, training: bool, momentum: float):
    if training:
        mu = jnp.mean(x, axis=(0, 1, 2))
        var = jnp.var(x, axis=(0, 1, 2))
        new = {"mean": momentum * st["mean"] + (1 - momentum) * mu,
               "var": momentum * st["var"] + (1 - momentum) * var}
    else:
        mu, var = st["mean"], st["var"]
        new = st
    y = (x - mu) * jax.lax.rsqrt(var + 1e-5)
    return y * p["scale"] + p["bias"], new


def make_engine(cfg: ResNetConfig, backend: Optional[str] = None,
                fused: bool = True, mesh=None, data_axis="data",
                model_axis=None,
                blocks: Optional[tuple] = None,
                autotune: bool = False,
                autotune_opts: Optional[dict] = None,
                warmup: Optional[tuple] = None,
                plan=None) -> ConvEngine:
    """Build the config's ConvEngine.

    ``backend`` overrides the eligible-conv backend (e.g.
    ``"winograd_int8"`` to serve a trained checkpoint through the Pallas
    kernels without touching model code). ``fused=False`` forces the
    staged int8 pipeline (bit-identical; for benchmarking the fusion
    win). ``mesh`` serves prepared+calibrated int8 layers sharded across
    the mesh: tiles over ``data_axis`` (tile-slab parallelism) and —
    with ``model_axis`` set — each conv's Cout over that axis too (conv
    tensor parallelism: weight shards per device, one all_gather per
    layer — see ``ConvEngine``); ``blocks`` manually overrides the
    Pallas GEMM tile blocks; ``autotune=True`` instead searches the
    block split per layer shape at calibration time and caches the
    winners in the packed state (``repro.conv.autotune``).

    ``warmup=(params, state, geometries)`` additionally builds the
    jitted serving forward (``serving_forward``), stores it on the
    engine as ``serve_fn``, and runs ``ConvEngine.warmup`` over the
    given ``(batch, 32, 32, 3)`` geometries so the first request of any
    registered serving shape is not a compile storm. Only meaningful
    when the engine already holds its final serving state at build time
    — a restore-from-checkpoint flow should instead call
    ``engine.warmup(...)`` after ``import_state``.

    ``plan`` is a measured per-layer ``repro.conv.planner.Plan``: planned
    layers route by their plan entry (possibly a different F(m, r)/base/
    Hadamard width per layer) and the policy's hand thresholds become
    the fallback for unplanned layers. None (default) keeps pure policy
    routing — the pre-planner behavior, bit for bit.
    """
    if not cfg.use_winograd or cfg.wino is None:
        eng = ConvEngine(cfg.wino,
                         ConvPolicy(backend="direct", fallback="direct"),
                         plan=plan)
    else:
        backend = backend or cfg.conv_backend or "winograd_fakequant"
        eng = ConvEngine(cfg.wino, ConvPolicy(backend=backend),
                         fused=fused, mesh=mesh,
                         data_axis=data_axis, model_axis=model_axis,
                         blocks=blocks, autotune=autotune,
                         autotune_opts=autotune_opts, plan=plan)
    if warmup is not None:
        params, state, geometries = warmup
        eng.serve_fn = serving_forward(params, state, cfg, eng)
        eng.warmup(geometries)
    return eng


def serving_forward(params, state, cfg: ResNetConfig, engine: ConvEngine):
    """The jitted online-serving callable: images → logits, inference
    mode, closed over one engine. Build it ONCE per engine and reuse —
    each call to this factory is a fresh ``jax.jit`` with an empty
    compile cache, so re-wrapping would re-compile (and break the
    serving loop's zero-recompile accounting)."""
    def serve_resnet(im):
        return forward(params, state, im, cfg, training=False,
                       engine=engine)[0]

    return jax.jit(serve_resnet)


def conv_layers(params, cfg: ResNetConfig):
    """Yield (layer_name, weights, stride) for every engine-routed conv —
    the iteration order of ``forward``, for prepare()/calibration."""
    yield "stem", params["stem"], 1
    for nm, _, _, stride in _iter_blocks(cfg):
        p = params["blocks"][nm]
        yield f"{nm}.conv1", p["conv1"], stride
        yield f"{nm}.conv2", p["conv2"], 1
        if "proj" in p:
            yield f"{nm}.proj", p["proj"], stride


def layer_geoms(cfg: ResNetConfig, batch: int,
                image_hw: int = 32) -> list[LayerGeom]:
    """Static per-layer geometry of every engine-routed conv — the
    planner's layer menu (``repro.conv.planner.build_plan``), one
    ``LayerGeom`` per ``conv_layers`` entry in the same order. Spatial
    extent halves at every stride-2 block (SAME padding), exactly the
    shapes ``forward`` feeds the engine."""
    hw = image_hw
    geoms = [LayerGeom("stem", (batch, hw, hw, 3), cfg.widths[0])]
    for nm, cin, cout, stride in _iter_blocks(cfg):
        hw_out = -(-hw // stride)       # ceil: SAME-padding output extent
        geoms.append(LayerGeom(f"{nm}.conv1", (batch, hw, hw, cin), cout,
                               stride=stride))
        geoms.append(LayerGeom(f"{nm}.conv2", (batch, hw_out, hw_out, cout),
                               cout))
        if stride != 1 or cin != cout:
            geoms.append(LayerGeom(f"{nm}.proj", (batch, hw, hw, cin), cout,
                                   kernel_size=1, stride=stride))
        hw = hw_out
    return geoms


def forward(params, state, images, cfg: ResNetConfig, training: bool = False,
            engine: Optional[ConvEngine] = None):
    """images: (B, 32, 32, 3) → logits (B, classes), new_state.

    ``engine`` carries prepared/calibrated serving state; omitted, a
    stateless engine is built from the config (training path). Batch norm,
    the residual add and the head are traced under the stage scopes of
    ``repro.telemetry``, beside the engine's per-layer conv scopes.
    """
    if engine is None:
        engine = make_engine(cfg)
    flex = params.get("wino_flex")
    mom = cfg.bn_momentum
    new_state = {"blocks": {}}

    x = engine.conv2d(images, params["stem"], layer="stem", flex=flex)
    with jax.named_scope(f"stem/{telemetry.BN}"):
        x, new_state["bn_stem"] = _bn(x, params["bn_stem"],
                                      state["bn_stem"], training, mom)
        x = jax.nn.relu(x)

    for nm, cin, cout, stride in _iter_blocks(cfg):
        p, st = params["blocks"][nm], state["blocks"][nm]
        ns = {}
        h = engine.conv2d(x, p["conv1"], layer=f"{nm}.conv1", stride=stride,
                          flex=flex)
        with jax.named_scope(f"{nm}.conv1/{telemetry.BN}"):
            h, ns["bn1"] = _bn(h, p["bn1"], st["bn1"], training, mom)
            h = jax.nn.relu(h)
        h = engine.conv2d(h, p["conv2"], layer=f"{nm}.conv2", flex=flex)
        with jax.named_scope(f"{nm}.conv2/{telemetry.BN}"):
            h, ns["bn2"] = _bn(h, p["bn2"], st["bn2"], training, mom)
        if "proj" in p:
            sc = engine.conv2d(x, p["proj"], layer=f"{nm}.proj",
                               stride=stride, flex=flex)
            with jax.named_scope(f"{nm}.proj/{telemetry.BN}"):
                sc, ns["bn_proj"] = _bn(sc, p["bn_proj"], st["bn_proj"],
                                        training, mom)
        else:
            sc = x
        with jax.named_scope(f"{nm}/{telemetry.RELU_ADD}"):
            x = jax.nn.relu(h + sc)
        new_state["blocks"][nm] = ns

    with jax.named_scope(telemetry.HEAD):
        x = jnp.mean(x, axis=(1, 2))
        logits = x @ params["head"] + params["head_b"]
    return logits, new_state


def loss_fn(params, state, batch, cfg: ResNetConfig, training: bool = True):
    logits, new_state = forward(params, state, batch["images"], cfg,
                                training)
    labels = batch["labels"]
    lse = jax.nn.logsumexp(logits, -1)
    ll = jnp.take_along_axis(logits, labels[:, None], -1)[:, 0]
    loss = jnp.mean(lse - ll)
    acc = jnp.mean((jnp.argmax(logits, -1) == labels).astype(jnp.float32))
    return loss, (new_state, acc)
