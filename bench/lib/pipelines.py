"""The thesis' Ch. 3 image-processing modules (arXiv:2010.14057), as the
benchmark's workflow configuration runs them.

Three pipelines over batches of images (the thesis used Flavia, 2KCanola and
4KCanola):

  leaves_recognition: descriptor -> matching
  segmentation:       transform -> estimate -> fit -> analyze
  clustering:         transform -> estimate -> fit(n_clusters=12) -> analyze

Every module is one jitted program, so each shape and tool state compiles
once, in set-up.  ``compose`` runs a pipeline's modules one after another
with no store, policy or scheduler: the plain reference for a workflow.
"""
from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp


@jax.jit
def transform(x):
    """Colour conversion and normalization (transformation stage)."""
    gray = x @ jnp.asarray([0.299, 0.587, 0.114])
    g = (gray - gray.mean()) / (gray.std() + 1e-6)
    return jnp.stack([g, jnp.square(g), jnp.sqrt(jnp.abs(g))], axis=-1)


@jax.jit
def estimate(x):
    """Feature extraction: a small convolution pyramid (estimation stage)."""
    k = jnp.ones((5, 5, x.shape[-1], 8), x.dtype) / 25.0
    h = jax.lax.conv_general_dilated(
        x, k, (2, 2), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC")
    )
    h = jax.nn.relu(h)
    k2 = jnp.ones((3, 3, 8, 16), x.dtype) / 9.0
    h = jax.lax.conv_general_dilated(
        h, k2, (2, 2), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC")
    )
    return jax.nn.relu(h).reshape(x.shape[0], -1)


@partial(jax.jit, static_argnames=("n_clusters", "iters"))
def fit(feats, n_clusters=8, iters=80):
    """k-means by Lloyd iterations (model fitting, the expensive stage)."""
    cent = feats[:n_clusters]

    def step(c, _):
        d = jnp.sum(jnp.square(feats[:, None] - c[None]), axis=-1)
        onehot = jax.nn.one_hot(jnp.argmin(d, axis=1), n_clusters, dtype=feats.dtype)
        return (onehot.T @ feats) / jnp.maximum(onehot.sum(0)[:, None], 1.0), None

    cent, _ = jax.lax.scan(step, cent, None, length=iters)
    d = jnp.sum(jnp.square(feats[:, None] - cent[None]), axis=-1)
    return {"centroids": cent, "assign": jnp.argmin(d, axis=1), "feats": feats}


@partial(jax.jit, static_argnames="detail")
def analyze(state, detail=1):
    """Cluster statistics; ``detail`` sets how many quantiles the report has."""
    feats, assign = state["feats"], state["assign"]
    k = state["centroids"].shape[0]
    onehot = jax.nn.one_hot(assign, k, dtype=feats.dtype)
    sizes = onehot.sum(0)
    spread = (onehot.T @ jnp.square(feats)).sum(-1) / jnp.maximum(sizes, 1.0)
    out = {"sizes": sizes, "spread": spread}
    for q in range(1, detail):
        out[f"q{q}"] = jnp.percentile(spread, 100 * q / detail)
    return out


@jax.jit
def descriptor(x):
    """Leaf descriptor: dense histograms of oriented gradients."""
    gray = x @ jnp.asarray([0.299, 0.587, 0.114])
    gx = jnp.diff(gray, axis=1, prepend=gray[:, :1])
    gy = jnp.diff(gray, axis=2, prepend=gray[:, :, :1])
    mag = jnp.sqrt(gx**2 + gy**2)
    ang = jnp.arctan2(gy, gx)
    bins = jnp.linspace(-np.pi, np.pi, 17)
    h, w = gray.shape[1] // 8, gray.shape[2] // 8
    hists = []
    for i in range(16):
        m = ((ang >= bins[i]) & (ang < bins[i + 1])).astype(gray.dtype)
        hists.append((mag * m).reshape(gray.shape[0], h, 8, w, 8).sum((2, 4)))
    return jnp.stack(hists, -1).reshape(gray.shape[0], -1)


@jax.jit
def matching(desc):
    """All-pairs descriptor distances and each image's five nearest."""
    d2 = (
        jnp.sum(desc**2, 1)[:, None]
        - 2 * desc @ desc.T
        + jnp.sum(desc**2, 1)[None, :]
    )
    return {"knn": jnp.argsort(d2, axis=1)[:, 1:6], "score": jnp.sort(d2, axis=1)[:, 1:6].mean()}


MODULES = {
    "transform": transform,
    "estimate": estimate,
    "fit": fit,
    "analyze": analyze,
    "descriptor": descriptor,
    "matching": matching,
}


def register(client, modules=MODULES) -> None:
    """Register the modules on a ``repro.api.Client`` with their defaults."""
    from repro.core import ModuleSpec

    defaults = {"fit": {"n_clusters": 8, "iters": 80}, "analyze": {"detail": 1}}
    for name, fn in modules.items():
        client.register(ModuleSpec(name, lambda x, _f=fn, **kw: _f(x, **kw), defaults.get(name, {})))


def compose(x, steps, *, round_to=None):
    """Run ``steps`` ([(module, params)]) on ``x`` directly.  ``round_to``
    rounds every module's input to that dtype first (the control)."""
    for name, params in steps:
        if round_to is not None:
            x = jax.tree_util.tree_map(
                lambda a: a.astype(round_to).astype(a.dtype)
                if jnp.issubdtype(a.dtype, jnp.floating) else a,
                x,
            )
        x = MODULES[name](x, **params)
    return x


@partial(jax.jit, static_argnames=("shape",))
def make_images(key, shape):
    """One dataset of images, uniform in [0, 1), made on the device."""
    return jax.random.uniform(key, shape, jnp.float32)
