"""Weights from `--seed`, made on the device in ONE jitted call, in the
type they are used in. The program's model and the plain reference are both
given what this makes; neither makes its own.

A leaf's values depend only on (seed, leaf name, shape, kind, scale, dtype),
so the reference can make one layer at a time, after the program's copy is
freed, and get the same numbers. Seed and names enter as arguments, so one
compiled program serves every seed (and every layer of one shape).
"""
import zlib

import numpy as np


def _name_ids(names):
    return np.asarray([zlib.crc32(n.encode()) for n in names], np.uint32)


def _seed_words(seed: int):
    seed = int(seed)
    return np.asarray([seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF], np.uint32)


def make(specs: dict, seed: int, dtype, sharding=None) -> dict:
    """specs: {leaf name: (shape, kind, scale)}; kind is `normal` (mean 0,
    std scale), `normal_pad0` (the same with row 0 zeroed: a padding row),
    `ones` or `zeros`. Returns {leaf name: array of `dtype`}."""
    import jax
    import jax.numpy as jnp

    names = sorted(specs)
    shapes = tuple((tuple(specs[n][0]), specs[n][1], float(specs[n][2])) for n in names)

    def build(words, ids):
        key = jax.random.fold_in(jax.random.PRNGKey(words[0]), words[1])
        out = []
        for i, (shape, kind, scale) in enumerate(shapes):
            if kind == "ones":
                out.append(jnp.ones(shape, dtype))
            elif kind == "zeros":
                out.append(jnp.zeros(shape, dtype))
            elif kind in ("normal", "normal_pad0"):
                k = jax.random.fold_in(key, ids[i])
                v = jax.random.normal(k, shape, jnp.float32) * scale
                if kind == "normal_pad0":
                    v = v.at[0].set(0.0)
                out.append(v.astype(dtype))
            else:
                raise ValueError(f"unknown init kind {kind!r}")
        return out

    kw = {}
    if sharding is not None:
        kw["out_shardings"] = [sharding.get(n) for n in names] if isinstance(sharding, dict) else sharding
    vals = jax.jit(build, **kw)(_seed_words(seed), _name_ids(names))
    return dict(zip(names, vals))
