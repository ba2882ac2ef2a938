"""Seeded weights, made once on the device, for the program and the reference.

:func:`canonical` draws every weight of a configuration file from the
seed in a flat layout of the benchmark's own, which the configuration's
family (``families/``) gives: float32, per-layer arrays stacked on a
leading layer axis, unpadded vocabulary.  The reference (``reference/``)
reads that layout; the family's ``program_params`` re-arranges the same
arrays into the tree the program's ``repro.models.lm`` takes, so both
sides compute with the same numbers while neither takes anything that
the other made.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

import families


def key(seed: int):
    """A PRNG key from a seed of any size: JAX keeps 32 bits of an int, so
    the high bits are folded in."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0x7FFFFFFF)


def canonical(cfg: dict, base) -> dict:
    """Every weight of ``cfg`` (float32), as its family's
    ``weight_shapes`` lists them, from the key ``base`` (:func:`key` of
    the seed).  Call under ``jax.jit`` so the draws happen on the device
    in one program."""
    shapes = families.of(cfg).weight_shapes(cfg)
    out = {}
    for i, (name, (shape, scale)) in enumerate(sorted(shapes.items())):
        k = jax.random.fold_in(base, i)
        if scale is None:                          # norm scales
            out[name] = 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
        elif isinstance(scale, float):             # embeddings
            out[name] = scale * jax.random.normal(k, shape, jnp.float32)
        else:                                      # dense: N(0, 1/fan_in)
            out[name] = (jax.random.truncated_normal(k, -2.0, 2.0, shape,
                                                     jnp.float32)
                         / math.sqrt(scale))
    return out
