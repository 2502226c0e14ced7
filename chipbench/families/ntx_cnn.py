"""Chained CNN family: ``NetworkGraph.chain`` graphs of conv, ReLU, max
pool, flatten and a fully connected head, trained with SGD and momentum.

The configuration file lists the layers; this module turns them into the
program's specs. The reference is a plain ``jax.numpy`` chain of
``lax.conv_general_dilated`` (NHWC x HWIO), ReLU, ``reduce_window`` max
pools, a row-major flatten and a matmul, with mean softmax cross-entropy,
independent of the program's lowering and kernels.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench import gen, refmath

UNIT = "images"


def _walk(cfg: dict):
    """(name, kind, attrs, in_shape, out_shape) per layer."""
    cur = tuple(cfg["config"]["image"])
    for entry in cfg["config"]["layers"]:
        name, kind = entry[0], entry[1]
        a = entry[2] if len(entry) > 2 else {}
        if kind == "conv":
            h, w, c = cur
            oh = (h + 2 * a["padding"] - a["k"]) // a["stride"] + 1
            ow = (w + 2 * a["padding"] - a["k"]) // a["stride"] + 1
            nxt = (oh, ow, a["cout"])
        elif kind == "maxpool":
            h, w, c = cur
            nxt = ((h - a["window"]) // a["stride"] + 1,
                   (w - a["window"]) // a["stride"] + 1, c)
        elif kind == "flatten":
            nxt = (cur[0] * cur[1] * cur[2],)
        elif kind == "fc":
            nxt = (a["cout"],)
        elif kind == "relu":
            nxt = cur
        else:
            raise ValueError(f"unknown layer kind {kind!r}")
        yield name, kind, a, cur, nxt
        cur = nxt


def build(cfg: dict, mix: dict):
    from repro.lower import Conv2dSpec, MatmulSpec, MaxPool2dSpec, NetworkGraph

    B = mix["batch"]
    layers = []
    for name, kind, a, cur, _ in _walk(cfg):
        if kind == "conv":
            spec = Conv2dSpec(cur[0], cur[1], cur[2], a["k"], a["k"], a["cout"],
                              stride=a["stride"], padding=a["padding"])
        elif kind == "maxpool":
            spec = MaxPool2dSpec(cur[0], cur[1], cur[2], window=a["window"],
                                 stride=a["stride"])
        elif kind == "fc":
            spec = MatmulSpec(B, a["cout"], cur[0])
        else:
            spec = kind  # "relu" / "flatten": the chain builder expands them
        layers.append((name, spec))
    t = cfg["train"]
    return NetworkGraph.chain(cfg["name"], B, tuple(cfg["config"]["image"]), layers,
                              lr=t["lr"], momentum=t["momentum"])


def units_per_step(mix: dict) -> int:
    return mix["batch"]


def pool(cfg: dict, mix: dict, seed: int):
    c = cfg["config"]
    return gen.image_pool(mix, tuple(c["image"]), c["num_classes"], seed)


def encode(item, graph):
    """Images go to the program as they are; the labels it one-hots itself."""
    return item


def norm_params(cfg: dict) -> set[str]:
    return set()


def make_loss(cfg: dict, mix: dict, precision: str):
    def conv(stride: int, pad: int):
        return refmath.bilinear(lambda x, w, precision: jax.lax.conv_general_dilated(
            x, w, window_strides=(stride, stride), padding=((pad, pad), (pad, pad)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=precision), precision)

    mm = refmath.bilinear(jnp.matmul, precision)
    layers = [(name, kind, a, conv(a["stride"], a["padding"]) if kind == "conv" else None)
              for name, kind, a, _, _ in _walk(cfg)]

    def loss(p, images, labels):
        h = images
        for name, kind, a, conv_fn in layers:
            if kind == "conv":
                h = conv_fn(h, p[f"w_{name}"])
            elif kind == "relu":
                h = jnp.maximum(h, 0.0)
            elif kind == "maxpool":
                w, s = a["window"], a["stride"]
                h = jax.lax.reduce_window(h, -jnp.inf, jax.lax.max,
                                          (1, w, w, 1), (1, s, s, 1), "VALID")
            elif kind == "flatten":
                h = h.reshape(h.shape[0], -1)
            elif kind == "fc":
                h = mm(h, p[f"w_{name}"])
        return refmath.xent(h, labels)

    return loss


def ref_inputs(cfg: dict, item):
    images, labels = item
    return jnp.asarray(images, jnp.float32), jnp.asarray(labels, jnp.int32)


def plant(cfg: dict, mix: dict, item, fault: str):
    """``"half"``: the second half of the batch left out; ``"token"``: the
    first image's label altered where it is produced."""
    images, labels = item
    if fault == "half":
        n = mix["batch"] // 2
        return dict(mix, batch=n), (images[:n], labels[:n])
    if fault == "token":
        labels = labels.copy()
        labels[0] = (labels[0] + 1) % cfg["config"]["num_classes"]
        return mix, (images, labels)
    raise ValueError(f"unknown fault {fault!r}")
