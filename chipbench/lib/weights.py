"""Random weights from the seed, made by the benchmark, and their hand-over
to the program.

The benchmark owns the weights: ``make_weights`` builds them on the device
in one jitted call, in float32 (the program's stored parameter dtype), in
the flat layout of the configuration's reference module, whose weight
table (``shapes``, ``init``) it follows.  ``program_tree`` nests the same
arrays (no copy) into the layout the program's ``LM.init`` returns, by the
module's ``PROGRAM_LEAF``, checked leaf by leaf against ``jax.eval_shape``
of it: a program whose parameters differ from the configuration is
refused here, before any run.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A PRNG key from a whole number of any size (past 32 bits too)."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def make_weights(spec, vocab_rows: int, seed: int) -> dict:
    """Every weight of ``spec`` from ``seed``, in one jitted call on the
    default device: ``mean + std * normal`` by the reference's table."""
    ref = spec.reference
    table = {name: (shape, *ref.init(spec, name, shape))
             for name, shape in ref.shapes(spec, vocab_rows).items()}

    def gen(key):
        out = {}
        for name, (shape, mean, std) in table.items():
            k = jax.random.fold_in(key, zlib.crc32(name.encode()))
            w = std * jax.random.normal(k, shape, jnp.float32)
            out[name] = mean + w if mean else w
        return out

    return jax.jit(gen)(seed_key(seed))


def program_tree(weights: dict, program_shapes, program_leaf: dict):
    """``weights`` nested as the program's parameter tree, each leaf the
    weight that ``program_leaf`` names for its (parent key, leaf key).
    Raises when the program has a parameter the benchmark does not make,
    or one of another shape or dtype."""
    used = set()

    def leaf(path, sd):
        keys = tuple(getattr(p, "key", None) for p in path)
        name = program_leaf.get(keys[-2:])
        if name is None or name not in weights:
            raise ValueError(f"the program has a parameter {keys} that the "
                             f"configuration does not give")
        w = weights[name]
        if tuple(w.shape) != tuple(sd.shape) or w.dtype != sd.dtype:
            raise ValueError(f"program parameter {keys} is {sd.shape} "
                             f"{sd.dtype}; the configuration gives "
                             f"{w.shape} {w.dtype}")
        used.add(name)
        return w

    tree = jax.tree_util.tree_map_with_path(leaf, program_shapes)
    unused = set(weights) - used
    if unused:
        raise ValueError(f"the program has no parameter for {sorted(unused)}")
    return tree
