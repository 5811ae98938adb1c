"""Random weights from the seed, made by the benchmark, and their hand-over
to the program.

The benchmark owns the weights: ``make_weights`` builds them on the device
in one jitted call, in float32 (the program's stored parameter dtype), in
the benchmark's own flat layout, which ``lib.reference`` reads.
``program_tree`` nests the same arrays (no copy) into the layout the
program's ``LM.init`` returns, checked leaf by leaf against
``jax.eval_shape`` of it: a program whose parameters differ from the
configuration is refused here, before any run.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

#: (parent key, leaf key) of a program parameter -> the benchmark's name
PROGRAM_LEAF = {
    ("embed", "table"): "embed",
    ("final_norm", "scale"): "final_norm",
    ("norm1", "scale"): "ln1",
    ("norm2", "scale"): "ln2",
    ("attn", "wq"): "wq", ("attn", "wk"): "wk", ("attn", "wv"): "wv",
    ("attn", "wo"): "wo", ("attn", "bq"): "bq", ("attn", "bk"): "bk",
    ("attn", "bv"): "bv",
    ("mlp", "w_gate"): "mlp_gate", ("mlp", "w_up"): "mlp_up",
    ("mlp", "w_down"): "mlp_down",
    ("moe", "router"): "router", ("moe", "w_gate"): "expert_gate",
    ("moe", "w_up"): "expert_up", ("moe", "w_down"): "expert_down",
}


def shapes(spec, vocab_rows: int) -> dict[str, tuple]:
    """The benchmark's weight shapes for ``spec``; the embedding holds
    ``vocab_rows >= vocab_size`` rows (the rows past the vocabulary are
    never looked up and never ranked)."""
    L, d = spec.num_hidden_layers, spec.hidden_size
    h, hkv, hd = (spec.num_attention_heads, spec.num_key_value_heads,
                  spec.head_dim)
    f = spec.intermediate_size
    out = {"embed": (vocab_rows, d), "final_norm": (d,),
           "ln1": (L, d), "ln2": (L, d),
           "wq": (L, d, h, hd), "wk": (L, d, hkv, hd), "wv": (L, d, hkv, hd),
           "wo": (L, h, hd, d)}
    if spec.data.get("attention_bias"):
        out.update(bq=(L, h, hd), bk=(L, hkv, hd), bv=(L, hkv, hd))
    if spec.is_moe:
        e = spec.num_local_experts
        out.update(router=(L, d, e), expert_gate=(L, e, d, f),
                   expert_up=(L, e, d, f), expert_down=(L, e, f, d))
    else:
        out.update(mlp_gate=(L, d, f), mlp_up=(L, d, f), mlp_down=(L, f, d))
    return out


def _std(spec, name: str, shape: tuple) -> float:
    """Fan-in scaled normals for matrices.  The embedding at the published
    ``initializer_range``: with the head tied, an embedding of unit scale
    makes every position's top logit its own input token, and greedy
    decoding then repeats it whatever the arithmetic."""
    if name == "embed":
        return spec.initializer_range
    if name in ("bq", "bk", "bv"):
        return 0.1
    if name in ("wq", "wk", "wv"):           # (L, d, heads, head_dim)
        fan_in = shape[-3]
    elif name == "wo":                       # (L, heads, head_dim, d)
        fan_in = shape[-3] * shape[-2]
    else:                                    # (..., fan_in, fan_out)
        fan_in = shape[-2]
    return fan_in ** -0.5


def seed_key(seed: int):
    """A PRNG key from a whole number of any size (past 32 bits too)."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def make_weights(spec, vocab_rows: int, seed: int) -> dict:
    """Every weight of ``spec`` from ``seed``, in one jitted call on the
    default device."""
    table = shapes(spec, vocab_rows)

    def gen(key):
        out = {}
        for name, shape in table.items():
            k = jax.random.fold_in(key, zlib.crc32(name.encode()))
            if name in ("ln1", "ln2", "final_norm"):
                out[name] = 1.0 + 0.1 * jax.random.normal(k, shape,
                                                          jnp.float32)
            else:
                out[name] = _std(spec, name, shape) * jax.random.normal(
                    k, shape, jnp.float32)
        return out

    return jax.jit(gen)(seed_key(seed))


def program_tree(weights: dict, program_shapes):
    """``weights`` nested as the program's parameter tree.  Raises when
    the program has a parameter the benchmark does not make, or one of
    another shape or dtype."""
    used = set()

    def leaf(path, sd):
        keys = tuple(getattr(p, "key", None) for p in path)
        name = PROGRAM_LEAF.get(keys[-2:])
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
