"""Plain reference of the served ViTDet forward, in straightforward jnp.

It follows the published ViTDet description as the served model
realises it: a pre-norm ViT (learned absolute positions, no relative
bias) whose blocks come in ``n_subsets`` groups of window blocks closed
by one global block, a simple feature pyramid, and an anchor-free head
decoded at every location.  Mixed resolution follows the ViTMAlis
paper: every decision region of ``r = w * d`` patches is FULL (its
``d^2`` native windows), LOW (one window of the ``d``-pooled image, with
``d``-pooled positions) or REUSE (absent until the restoration point,
where the session's earlier restored features are spliced back).  At
the restoration point LOW tokens are repeated ``d x d`` (nearest
neighbour).

Nothing here imports the program.  Instead of packing a variable-length
sequence, every frame computes all native and all pooled windows; the
global blocks before restoration mask their keys to the windows that
the plan transmits, so one compiled reference serves every plan.  Work
on windows that the plan does not transmit never reaches a result.

The reference runs float32 as plain ``jnp`` does, at the default matmul
precision: on a TPU a float32 matmul is one bfloat16 pass with float32
accumulation, which is the arithmetic the configurations state for the
served lane.  The control (``control=True``) runs the same code with
weights, inputs and activations stored in bfloat16.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

FULL, LOW, REUSE = 0, 1, 2
STRIDES = (8, 16, 32)


@dataclass(frozen=True)
class Geometry:
    """Sizes the reference needs, read from a configuration file."""
    img: int            # square input side, pixels
    patch: int
    window: int         # w, in patches
    down: int           # d
    d_model: int
    n_heads: int
    head_dim: int
    n_layers: int
    n_subsets: int
    n_classes: int
    eps: float

    @property
    def grid(self) -> int:
        return self.img // self.patch

    @property
    def region(self) -> int:
        return self.window * self.down

    @property
    def regions_side(self) -> int:
        return self.grid // self.region

    @property
    def n_regions(self) -> int:
        return self.regions_side ** 2

    @property
    def per_subset(self) -> int:
        return self.n_layers // self.n_subsets

    @classmethod
    def from_config(cls, c: Dict) -> "Geometry":
        return cls(img=c["img_size"], patch=c["patch_size"],
                   window=c["window_size"], down=c["downsample"],
                   d_model=c["d_model"], n_heads=c["n_heads"],
                   head_dim=c["head_dim"], n_layers=c["n_layers"],
                   n_subsets=c["n_subsets"], n_classes=c["n_classes"],
                   eps=c["norm_eps"])


# ---------------------------------------------------------------------------
# building blocks


def _mm(a, b, precision):
    return jnp.matmul(a, b, precision=precision)


def layer_norm(x, p, eps):
    x32 = x.astype(jnp.float32)
    mu = x32.mean(-1, keepdims=True)
    var = ((x32 - mu) ** 2).mean(-1, keepdims=True)
    y = (x32 - mu) / jnp.sqrt(var + eps)
    return (y * p["w"].astype(jnp.float32)
            + p["b"].astype(jnp.float32)).astype(x.dtype)


def attention(x, p, g: Geometry, key_valid, precision):
    """Multi-head self-attention over the token axis -2 of ``x``
    (..., T, D).  ``key_valid``: None or (T,) bool."""
    H, dh = g.n_heads, g.head_dim
    q = _mm(x, p["w_q"], precision) + p["b_q"]
    k = _mm(x, p["w_k"], precision) + p["b_k"]
    v = _mm(x, p["w_v"], precision) + p["b_v"]
    lead = x.shape[:-2]
    T = x.shape[-2]

    def heads(t):
        return jnp.moveaxis(t.reshape(lead + (T, H, dh)), -2, -3)

    q, k, v = heads(q), heads(k), heads(v)          # (..., H, T, dh)

    def one_head(qkv):
        qh, kh, vh = qkv
        s = _mm(qh, jnp.swapaxes(kh, -1, -2), precision) / np.sqrt(dh)
        if key_valid is not None:
            s = jnp.where(key_valid, s, -jnp.inf)
        a = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(x.dtype)
        return _mm(a, vh, precision)

    # one head at a time keeps a 4096^2 score matrix per head in memory
    qh = jnp.moveaxis(q, -3, 0)
    kh = jnp.moveaxis(k, -3, 0)
    vh = jnp.moveaxis(v, -3, 0)
    o = jax.lax.map(one_head, (qh, kh, vh))         # (H, ..., T, dh)
    o = jnp.moveaxis(o, 0, -2)                      # (..., T, H, dh)
    o = o.reshape(lead + (T, H * dh))
    return _mm(o, p["w_o"], precision) + p["b_o"]


def block(x, p, g: Geometry, key_valid, precision):
    h = layer_norm(x, p["ln1"], g.eps)
    x = x + attention(h, p["attn"], g, key_valid, precision)
    h = layer_norm(x, p["ln2"], g.eps)
    u = jax.nn.gelu(_mm(h, p["ffn"]["w_up"], precision) + p["ffn"]["b_up"],
                    approximate=True)
    return x + _mm(u, p["ffn"]["w_down"], precision) + p["ffn"]["b_down"]


def patch_embed(img, p, patch, precision):
    """(H, W, 3) pixels -> (H/p, W/p, D): row-major (py, px, channel)
    patch vectors times the embedding matrix."""
    H, W, C = img.shape
    x = img.reshape(H // patch, patch, W // patch, patch, C)
    x = x.transpose(0, 2, 1, 3, 4).reshape(H // patch, W // patch, -1)
    return _mm(x, p["w"], precision) + p["b"]


def pool(x, d):
    """Mean over d x d blocks of the two leading spatial axes."""
    H, W = x.shape[:2]
    return x.reshape(H // d, d, W // d, d, *x.shape[2:]).mean(axis=(1, 3))


def to_windows(grid, w):
    """(S, S, D) -> (S/w * S/w, w*w, D), windows row-major, tokens
    row-major inside each window."""
    S, _, D = grid.shape
    n = S // w
    return grid.reshape(n, w, n, w, D).transpose(0, 2, 1, 3, 4).reshape(
        n * n, w * w, D)


def from_windows(win, S, w):
    n = S // w
    D = win.shape[-1]
    return win.reshape(n, n, w, w, D).transpose(0, 2, 1, 3, 4).reshape(
        S, S, D)


def region_of_full_window(g: Geometry) -> np.ndarray:
    """Region index of each native window (row-major window grid)."""
    n = g.grid // g.window
    i, j = np.meshgrid(np.arange(n), np.arange(n), indexing="ij")
    return ((i // g.down) * g.regions_side + (j // g.down)).reshape(-1)


def region_map(g: Geometry) -> np.ndarray:
    """(grid, grid) region index of every patch."""
    i, j = np.meshgrid(np.arange(g.grid), np.arange(g.grid), indexing="ij")
    return (i // g.region) * g.regions_side + (j // g.region)


# ---------------------------------------------------------------------------
# the forward, split at the restoration point


def _cast(tree, dtype):
    return jax.tree_util.tree_map(lambda a: a.astype(dtype), tree)


def embed(params, img, states, g: Geometry, precision, dtype):
    """Patch embed of the native and the pooled image, with positions,
    as windows: (tok (nW, w2, D), key_valid (nW * w2,)), where a key is
    valid if the plan transmits its window."""
    params = _cast(params, dtype)
    img = img.astype(dtype)
    w, d = g.window, g.down
    pos = params["pos_emb"]
    x_full = patch_embed(img, params["patch_embed"], g.patch,
                         precision) + pos
    x_low = patch_embed(pool(img, d), params["patch_embed"], g.patch,
                        precision) + pool(pos, d)
    tok = jnp.concatenate([to_windows(x_full, w), to_windows(x_low, w)])
    reg = jnp.asarray(region_of_full_window(g))
    valid_win = jnp.concatenate([states[reg] == FULL, states == LOW])
    return tok, jnp.repeat(valid_win, tok.shape[1])


def unwindow(tok, states, g: Geometry):
    """The (grid, grid, D) map the transmitted regions restore to: FULL
    regions from their native windows, LOW regions' pooled tokens
    repeated d x d.  REUSE regions' entries are meaningless here;
    :func:`restore` replaces them."""
    w, d = g.window, g.down
    n_full = (g.grid // w) ** 2
    full = from_windows(tok[:n_full], g.grid, w)
    low = from_windows(tok[n_full:], g.grid // d, w)
    up = jnp.repeat(jnp.repeat(low, d, axis=0), d, axis=1)
    rm = jnp.asarray(region_map(g))
    return jnp.where((states[rm] == LOW)[..., None], up, full)


def restore(fresh, previous, states, g: Geometry):
    """Restored features at the restoration point: transmitted regions
    from ``fresh``, REUSE regions from the session's ``previous``
    restored features."""
    rm = jnp.asarray(region_map(g))
    reuse = (states[rm] == REUSE)[..., None]
    return jnp.where(reuse, previous.astype(fresh.dtype), fresh)


class Runner:
    """The reference forward, one jitted layer at a time (a window
    block, a global block, the embed, the head), so that a whole model
    compiles as a handful of small programs and runs in blocks that fit
    beside whatever else the process holds."""

    def __init__(self, g: Geometry, beta: int, control: bool):
        precision = jax.lax.Precision.DEFAULT
        dtype = jnp.bfloat16 if control else jnp.float32
        self.g, self.beta, self.dtype = g, beta, dtype

        def win(p, tok):
            return block(tok, _cast(p, dtype), g, None, precision)

        def glob(p, tok, key_valid):
            D = tok.shape[-1]
            return block(tok.reshape(-1, D), _cast(p, dtype), g, key_valid,
                         precision).reshape(tok.shape)

        def head(p_norm, p_head, tok):
            feats = layer_norm(from_windows(tok, g.grid, g.window),
                               _cast(p_norm, dtype), g.eps)
            return head_decode(_cast(p_head, dtype), feats, precision)

        self._embed = jax.jit(lambda p, img, st: embed(
            {"patch_embed": p["patch_embed"], "pos_emb": p["pos_emb"]},
            img, st, g, precision, dtype))
        self._win = jax.jit(win)
        self._glob = jax.jit(glob)
        self._unwindow = jax.jit(lambda tok, st: unwindow(tok, st, g))
        self._to_windows = jax.jit(
            lambda x: to_windows(x.astype(dtype), g.window))
        self._head = jax.jit(head)
        self.restore = jax.jit(lambda f, prev, st: restore(f, prev, st, g))

    def _blocks(self, params, tok, lo, hi, key_valid):
        M = self.g.per_subset
        for idx in range(lo, hi):
            p = params["blocks"][idx]
            if idx % M == M - 1:
                tok = self._glob(p, tok, key_valid)
            else:
                tok = self._win(p, tok)
        return tok

    def pre(self, params, img, states):
        """Blocks up to the restoration point on the plan's transmitted
        windows; the (grid, grid, D) map of :func:`unwindow`."""
        tok, key_valid = self._embed(params, img, states)
        tok = self._blocks(params, tok, 0, self.beta * self.g.per_subset - 1,
                           key_valid)
        return self._unwindow(tok, states)

    def post(self, params, fresh, previous, states):
        """Restoration, the blocks after it and the head, decoded at
        every location of every level: (scores (A,), boxes (A, 4),
        classes (A,))."""
        tok = self._to_windows(self.restore(fresh, previous, states))
        tok = self._blocks(params, tok, self.beta * self.g.per_subset - 1,
                           self.g.n_layers, None)
        return self._head(params["final_norm"], params["head"], tok)

    def full(self, params, img):
        """A FULL plan served without a session: every block at full
        resolution."""
        states = jnp.full((self.g.n_regions,), FULL, jnp.int32)
        tok, _ = self._embed(params, img, states)
        n_full = (self.g.grid // self.g.window) ** 2
        tok = self._blocks(params, tok[:n_full], 0, self.g.n_layers, None)
        return self._head(params["final_norm"], params["head"], tok)


# ---------------------------------------------------------------------------
# pyramid + head + decode


def conv(x, p, precision):
    y = jax.lax.conv_general_dilated(
        x[None], p["w"], (1, 1), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=precision)
    return y[0] + p["b"]


def head_decode(p, feats, precision):
    up = jnp.repeat(jnp.repeat(feats, 2, axis=0), 2, axis=1)
    levels = [up, feats, pool(feats, 2)]
    scores, boxes, classes = [], [], []
    for i, x in enumerate(levels):
        x = conv(x, p["lateral"][i], precision)
        x = jax.nn.relu(conv(x, p["smooth"][i], precision))
        t = jax.nn.relu(conv(x, p["tower"], precision))
        cls = conv(t, p["cls"], precision).astype(jnp.float32)
        box = jax.nn.softplus(conv(t, p["box"], precision)
                              .astype(jnp.float32))
        ctr = conv(t, p["ctr"], precision).astype(jnp.float32)
        prob = jax.nn.sigmoid(cls) * jax.nn.sigmoid(ctr)
        S = STRIDES[i]
        n = x.shape[0]
        c = (jnp.arange(n, dtype=jnp.float32) + 0.5) * S
        cy, cx = jnp.meshgrid(c, c, indexing="ij")
        ltrb = box * S
        b = jnp.stack([cx - ltrb[..., 0], cy - ltrb[..., 1],
                       cx + ltrb[..., 2], cy + ltrb[..., 3]], -1)
        scores.append(prob.max(-1).reshape(-1))
        classes.append(prob.argmax(-1).reshape(-1))
        boxes.append(b.reshape(-1, 4))
    return (jnp.concatenate(scores), jnp.concatenate(boxes),
            jnp.concatenate(classes))


# ---------------------------------------------------------------------------
# entry point


@functools.lru_cache(maxsize=None)
def compiled(g: Geometry, beta: int, control: bool) -> Runner:
    """The reference (or, with ``control``, the bfloat16 control) for
    one geometry and restoration point."""
    return Runner(g, beta, control)


def tiles_to_grid(tiles: np.ndarray, g: Geometry) -> np.ndarray:
    """The served cache's (n_regions, d^2, w^2, D) tiles — regions
    row-major, a region's d x d windows row-major, a window's tokens
    row-major — as a (grid, grid, D) map."""
    n, w, d = g.regions_side, g.window, g.down
    D = tiles.shape[-1]
    x = tiles.reshape(n, n, d, d, w, w, D)
    x = x.transpose(0, 2, 4, 1, 3, 5, 6)
    return x.reshape(g.grid, g.grid, D)
