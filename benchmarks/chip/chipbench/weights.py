"""Seeded random weights for the served ViTDet, made on the device.

One jitted call draws a single truncated-normal vector from the seed and
cuts every leaf from it, in the dtype it is served in, laid out as the
program's parameter tree (dicts and lists keyed as
``vit_backbone.init_vitdet_params`` keys them, which a test checks).
Matrices are fan-in scaled; biases, norm gains and the head's class
prior are drawn too, so that a path that drops one of them shows in the
comparison with the reference.  One random op instead of one per leaf
keeps the compile short.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np


class _Leaf:
    """Shape, scale and mean of one leaf."""

    def __init__(self, shape, std, mean=0.0):
        self.shape, self.std, self.mean = tuple(shape), float(std), mean


def _dense(*shape):
    return _Leaf(shape, 1.0 / np.sqrt(float(np.prod(shape[:-1]))))


def _small(*shape, std=0.02, mean=0.0):
    return _Leaf(shape, std, mean)


def _norm(d):
    return {"w": _small(d, std=0.05, mean=1.0), "b": _small(d)}


def _spec(c: Dict):
    D, F = c["d_model"], c["d_ff"]
    q = c["n_heads"] * c["head_dim"]
    kv = c["n_kv_heads"] * c["head_dim"]
    grid = c["img_size"] // c["patch_size"]
    C, nc = c["out_channels"], c["n_classes"]

    def block():
        return {
            "ln1": _norm(D),
            "attn": {"w_q": _dense(D, q), "w_k": _dense(D, kv),
                     "w_v": _dense(D, kv), "w_o": _dense(q, D),
                     "b_q": _small(q), "b_k": _small(kv), "b_v": _small(kv),
                     "b_o": _small(D)},
            "ln2": _norm(D),
            "ffn": {"w_up": _dense(D, F), "b_up": _small(F),
                    "w_down": _dense(F, D), "b_down": _small(D)},
        }

    def conv(k, cin, cout, bias_mean=0.0):
        return {"w": _dense(k, k, cin, cout),
                "b": _small(cout, mean=bias_mean)}

    patch_dim = c["patch_size"] ** 2 * 3
    return {
        "patch_embed": {"w": _dense(patch_dim, D), "b": _small(D)},
        "pos_emb": _small(grid, grid, D),
        "blocks": [block() for _ in range(c["n_layers"])],
        "final_norm": _norm(D),
        "head": {
            "lateral": [conv(1, D, C) for _ in range(3)],
            "smooth": [conv(3, C, C) for _ in range(3)],
            "tower": conv(3, C, C),
            "cls": conv(3, C, nc, bias_mean=-2.0),
            "box": conv(3, C, 4),
            "ctr": conv(3, C, 1),
        },
    }


def _is_leaf(x):
    return isinstance(x, _Leaf)


@functools.lru_cache(maxsize=None)
def _init_fn(items, dtype_name):
    spec = _spec(dict(items))
    leaves, treedef = jax.tree_util.tree_flatten(spec, is_leaf=_is_leaf)
    sizes = [int(np.prod(l.shape)) for l in leaves]
    dtype = jnp.dtype(dtype_name)

    def init(key):
        z = jax.random.truncated_normal(key, -2.0, 2.0, (sum(sizes),),
                                        jnp.float32)
        out, off = [], 0
        for leaf, n in zip(leaves, sizes):
            x = z[off:off + n].reshape(leaf.shape) * leaf.std + leaf.mean
            out.append(x.astype(dtype))
            off += n
        return jax.tree_util.tree_unflatten(treedef, out)
    return jax.jit(init)


def make(c: Dict, seed32: int, dtype: str = "float32"):
    """The weight tree of model sizes ``c`` for a 32-bit seed."""
    fn = _init_fn(tuple(sorted(c.items())), dtype)
    return jax.block_until_ready(fn(jax.random.PRNGKey(seed32)))
