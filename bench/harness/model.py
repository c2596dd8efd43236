"""A configuration file, its architecture, and the weights made from a
seed.

The file uses the key names of the published ``config.json`` and names
its ``architecture``; ``architectures/<architecture>.py`` reads it (see
``architectures/dense_lm.py`` for what such a file gives).  A key that the
program runs at another value than the published one is listed under
``departures`` with the value it runs at.
"""
from __future__ import annotations

import hashlib
import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp

# Norm scales are drawn from this fixed key, not from the seed: the
# program's exported graph closes over them (models/opgraph_export.py,
# ``_norm_node``), so they enter the captured program as constants, and
# scales that changed with the seed would compile that program anew for
# every seed.
NORM_SCALE_SEED = 2312


def load_config(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_architecture(root: str, name: str):
    """The module ``bench/architectures/<name>.py`` under ``root``, loaded
    once per file."""
    path = os.path.realpath(os.path.join(root, "bench", "architectures",
                                         name + ".py"))
    modname = f"bench_arch_{name}_{hashlib.sha1(path.encode()).hexdigest()[:8]}"
    if modname in sys.modules:
        return sys.modules[modname]
    if not os.path.exists(path):
        raise ValueError(f"no architecture file {path}")
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[modname]
        raise
    return mod


def seed_key(seed: int):
    """A PRNG key from any whole number below 2**64."""
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} outside [0, 2**64)")
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def make_params(mcfg, seed: int):
    """Weights in the program's layout and type, made on the device in one
    jitted call from ``seed``.  Norm scales are 1 + noise and biases are
    non-zero, so the comparison sees both applied."""
    from repro.models import Model

    shapes = jax.eval_shape(Model(mcfg).init, jax.random.key(0))
    flat, tree = jax.tree_util.tree_flatten_with_path(shapes)

    def gen(key):
        norm_key = jax.random.key(NORM_SCALE_SEED)
        out = []
        for i, (path, sds) in enumerate(flat):
            leaf = path[-1].key
            k = jax.random.fold_in(norm_key if leaf == "scale" else key, i)
            z = jax.random.normal(k, sds.shape, jnp.float32)
            if leaf == "scale":
                v = 1.0 + 0.1 * z
            elif leaf == "b":
                v = 0.02 * z
            elif leaf == "table":
                v = 0.02 * z
            else:                               # "w": [..., d_in, d_out]
                v = z * sds.shape[-2] ** -0.5
            out.append(v.astype(sds.dtype))
        return jax.tree_util.tree_unflatten(tree, out)

    return jax.jit(gen)(seed_key(seed))
