"""Plain float32 reference of the MobileNetV2 of configs/<cnn>.json.

Straightforward ``jax.numpy`` / ``lax.conv`` at ``highest`` precision, with
no quantization, im2col or kernel: a 3x3 stem, inverted residual blocks
(1x1 expand, 3x3 depthwise, 1x1 linear project, a residual where shape
allows), a 1x1 head conv, global average pooling and a linear classifier
without bias (arXiv:1801.04381), with the stride plan the file gives.
BatchNorm normalizes with the batch's own mean and (biased) variance, as
in training; ReLU6 follows every BatchNorm but the project's.  It imports
nothing of the program.

``init_params`` gives the benchmark's weights, in the layout the program's
parameter and BatchNorm trees have, at the program's init scales (He
normal convs, unit BatchNorm scale, zero shift; running mean 0, var 1).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
tmap = jax.tree_util.tree_map


def _scaled(c: dict, ch: int) -> int:
    return max(8, int(ch * c["width"] + 0.5) // 8 * 8)


def blocks(c: dict) -> list:
    """``(name, expansion, cin, cout, stride)`` of each inverted residual."""
    out, cin, idx = [], _scaled(c, c["stem_channels"]), 0
    for t, ch, n, s in c["plan"]:
        ch = _scaled(c, ch)
        for bi in range(n):
            out.append((f"b{idx}", t, cin, ch, s if bi == 0 else 1))
            cin, idx = ch, idx + 1
    return out


def param_specs(c: dict) -> dict:
    """``{path: (shape, init)}`` of every weight; ``init`` is a normal's
    scale (He for convs), or "zeros" / "ones"."""
    specs = {}

    def conv(path, k, cin, cout, groups=1):
        fan_in = k * k * cin // groups
        specs[path] = ((k, k, cin // groups, cout), (2.0 / fan_in) ** 0.5)

    def bn(path, ch):
        specs[path + ("scale",)] = ((ch,), "ones")
        specs[path + ("bias",)] = ((ch,), "zeros")

    c0 = _scaled(c, c["stem_channels"])
    conv(("stem",), 3, c["channels"], c0)
    bn(("stem_bn",), c0)
    for name, t, cin, cout, _ in blocks(c):
        mid = cin * t
        if t != 1:
            conv((name, "expand"), 1, cin, mid)
            bn((name, "expand_bn"), mid)
        conv((name, "dw"), 3, mid, mid, groups=mid)
        bn((name, "dw_bn"), mid)
        conv((name, "project"), 1, mid, cout)
        bn((name, "project_bn"), cout)
    head = _scaled(c, c["head_channels"])
    conv(("head",), 1, blocks(c)[-1][3], head)
    bn(("head_bn",), head)
    specs[("fc",)] = ((head, c["num_classes"]), head ** -0.5)
    return specs


def init_leaf(key, c: dict, path: tuple):
    """The weight at ``path``, drawn from ``key`` as ``init_params`` does."""
    specs = param_specs(c)
    shape, init = specs[path]
    if init == "zeros":
        return jnp.zeros(shape, jnp.float32)
    if init == "ones":
        return jnp.ones(shape, jnp.float32)
    k = jax.random.fold_in(key, sorted(specs).index(path))
    return jax.random.normal(k, shape, jnp.float32) * init


def init_params(key, c: dict):
    """``(params, bn_state)`` from ``key`` (call under ``jax.jit``); the
    running statistics start at mean 0, variance 1."""
    params, state = {}, {}
    for path, (shape, _) in param_specs(c).items():
        node = params
        for name in path[:-1]:
            node = node.setdefault(name, {})
        node[path[-1]] = init_leaf(key, c, path)
        if path[-1] == "scale":
            node = state
            for name in path[:-2]:
                node = node.setdefault(name, {})
            node[path[-2]] = {"mean": jnp.zeros(shape), "var": jnp.ones(shape)}
    return params, state


def _conv(x, w, stride=1, groups=1):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME", feature_group_count=groups,
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HI)


def _bn(x, p, eps):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def logits(params, images, c):
    eps = c.get("bn_eps", 1e-5)
    relu6 = lambda x: jnp.clip(x, 0.0, 6.0)
    x = relu6(_bn(_conv(images, params["stem"], c["stem_stride"]),
                  params["stem_bn"], eps))
    for name, t, cin, cout, s in blocks(c):
        p, h = params[name], x
        if t != 1:
            h = relu6(_bn(_conv(h, p["expand"]), p["expand_bn"], eps))
        h = relu6(_bn(_conv(h, p["dw"], s, groups=h.shape[-1]), p["dw_bn"],
                      eps))
        h = _bn(_conv(h, p["project"]), p["project_bn"], eps)
        x = h + x if (s == 1 and cin == cout) else h
    x = relu6(_bn(_conv(x, params["head"]), params["head_bn"], eps))
    return jnp.dot(jnp.mean(x, axis=(1, 2)), params["fc"], precision=HI)


def loss(params, batch, c):
    z = logits(params, batch["images"], c)
    gold = jnp.take_along_axis(z, batch["labels"][:, None], 1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(z, -1) - gold)


def loss_and_grad(c: dict):
    """``f(params, batch) -> (loss, grads)`` of one training batch."""
    return jax.jit(jax.value_and_grad(lambda p, b: loss(p, b, c)))
