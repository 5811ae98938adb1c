"""Mesh-aware sharding rules: spec trees -> NamedShardings, batch specs,
and per-arch parallelism defaults."""
from __future__ import annotations

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ParallelConfig, ShapeConfig
from repro.models.common import MeshInfo


def use_mesh(mesh: Mesh):
    """Context manager installing ``mesh`` as the ambient mesh."""
    return jax.set_mesh(mesh)


def ambient_mesh():
    """The (abstract) mesh installed by :func:`use_mesh`, or None."""
    m = jax.sharding.get_abstract_mesh()
    return m if m.axis_names else None


def mesh_info(mesh: Mesh, fsdp: bool = False) -> MeshInfo:
    names = mesh.axis_names
    sizes = dict(zip(names, mesh.devices.shape))
    data_axes = tuple(a for a in ("pod", "data") if a in names)
    data = 1
    for a in data_axes:
        data *= sizes[a]
    return MeshInfo(data=data, model=sizes.get("model", 1),
                    data_axes=data_axes or ("data",), model_axis="model",
                    fsdp=fsdp)


def shardings_for(mesh: Mesh, spec_tree):
    """PartitionSpec tree -> NamedSharding tree (same structure)."""
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def batch_specs(cfg: ModelConfig, shape: ShapeConfig, minfo: MeshInfo):
    """PartitionSpecs for the input batch of one cell.

    The batch dim shards over the DP axes when divisible; ``long_500k``'s
    batch of 1 replicates (its parallelism lives in the seq-sharded KV cache
    instead — SP)."""
    dp = minfo.dp() if shape.global_batch % minfo.data == 0 else None
    if shape.kind == "train":
        if cfg.frontend == "audio_stub":
            return {"frames": P(dp, None, None), "labels": P(dp, None)}
        if cfg.frontend == "vision_stub":
            return {"patches": P(dp, None, None), "tokens": P(dp, None),
                    "labels": P(dp, None)}
        return {"tokens": P(dp, None), "labels": P(dp, None)}
    if shape.kind == "prefill":
        if cfg.frontend == "audio_stub":
            return {"frames": P(dp, None, None)}
        if cfg.frontend == "vision_stub":
            return {"patches": P(dp, None, None), "tokens": P(dp, None)}
        return {"tokens": P(dp, None)}
    # decode
    if cfg.frontend == "audio_stub":
        return {"token": P(dp, None, None), "pos": P()}
    return {"token": P(dp, None), "pos": P()}


def default_parallel(arch: str) -> ParallelConfig:
    """Per-arch parallelism defaults (DESIGN.md §5).

    FSDP (param + optimizer sharding over the data axes) for the archs whose
    training state exceeds a model-sharded chip's HBM."""
    fsdp = arch in ("qwen2.5-32b", "kimi-k2-1t-a32b", "stablelm-12b")
    return ParallelConfig(fsdp=fsdp, remat="block")
