"""The four built-in GEMM backends.

* ``analytic-gap8``  — the paper's calibrated GAP8 simulator (§3, Table 2):
  searches loop-order variants x register-feasible micro-kernels.  Predicts
  only.
* ``analytic-tpu``   — the TPU adaptation: TileTuner's search over Pallas
  ``(bm, bn, bk, grid-order)`` candidates.  Predicts only.
* ``pallas``         — plans exactly like ``analytic-tpu`` and executes the
  plan with the Pallas kernels (TPU or ``interpret=True``); off-TPU without
  interpret it falls back to the jnp reference, keeping SPMD lowering clean
  (same dispatch rule as the old ``kernels.ops.matmul``).
* ``reference``      — no tiling decision; executes the pure-jnp oracle.
  Its estimate is the whole-array (single-tile) cost — the model's lower
  bound on blocking, useful as a sanity baseline.
"""
from __future__ import annotations

from typing import Mapping, Sequence

import jax
import jax.numpy as jnp

from repro.core.autotune import tune_batch
from repro.core.hardware import MachineSpec
from repro.core.simulator import (
    best_microkernel_batch,
    search_batch,
    simulate,
)
from repro.core.tpu_model import GridOrder, TileConfig, estimate
from repro.core.variants import MicroKernel, Variant
from repro.gemm.api import GemmPlan, GemmProblem, VariantChoice
from repro.gemm.registry import Backend, register_backend

_JNP_DTYPE_TAGS = {"bfloat16": "bf16", "float32": "f32", "int8": "int8"}


def dtype_tag(dtype) -> str:
    """Map a jnp/numpy dtype to the cost models' dtype tag."""
    return _JNP_DTYPE_TAGS.get(jnp.dtype(dtype).name, "bf16")


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _pad_to(x, mults):
    pads = [(0, (m - d % m) % m) for d, m in zip(x.shape, mults)]
    if any(p[1] for p in pads):
        return jnp.pad(x, pads)
    return x


def _coerce_variant(v) -> Variant:
    return v if isinstance(v, Variant) else Variant(v)


def _coerce_mk(mk) -> MicroKernel:
    if isinstance(mk, MicroKernel):
        return mk
    return MicroKernel(int(mk[0]), int(mk[1]))


class AnalyticGap8Backend(Backend):
    """The paper's simulator instance: Table-2's exhaustive search.

    Planning is a bulk operation: ``make_plans`` scores the whole
    (problem x variant x micro-kernel) lattice through the batched simulator
    and argmin-selects per problem; ``make_plan`` is the one-problem case.
    """

    name = "analytic-gap8"
    executable = False
    default_machine = "gap8-fc"
    default_dtype = "int8"
    sweep_axes = frozenset({"variant", "micro_kernel"})

    def make_plan(self, problem: GemmProblem, machine: MachineSpec,
                  policy: str, options: Mapping) -> GemmPlan:
        return self.make_plans([problem], machine, policy, options)[0]

    def make_plans(self, problems: Sequence[GemmProblem],
                   machine: MachineSpec, policy: str,
                   options: Mapping) -> list[GemmPlan]:
        variant = options.get("variant")
        mk = options.get("micro_kernel")
        variants = ([_coerce_variant(variant)] if variant is not None
                    else list(Variant))
        probs = [p.as_problem() for p in problems]
        if mk is not None:
            if variant is None:
                raise ValueError(
                    "micro_kernel override requires an explicit variant")
            cbs = [simulate(machine, variants[0], _coerce_mk(mk), pr,
                            policy=policy) for pr in probs]
            source = "explicit"
        elif variant is not None:
            cbs = best_microkernel_batch(machine, variants[0], probs,
                                         policy=policy)
            source = "search"
        else:
            cbs = search_batch(machine, probs, variants, policy=policy)
            source = "search"
        return [GemmPlan(
            problem=p, backend=self.name, machine=machine.name,
            selection=VariantChoice(cb.variant, cb.micro_kernel, cb.blocking),
            cost=cb,
            provenance={"source": source, "method": "best_microkernel",
                        "policy": policy,
                        "variants": [v.value for v in variants]},
        ) for p, cb in zip(problems, cbs)]


class AnalyticTpuBackend(Backend):
    """TileTuner's analytic search over the Pallas tiling design space."""

    name = "analytic-tpu"
    executable = False
    default_machine = "tpu-v5e"
    default_dtype = "bf16"

    def make_plan(self, problem: GemmProblem, machine: MachineSpec,
                  policy: str, options: Mapping) -> GemmPlan:
        return self.make_plans([problem], machine, policy, options)[0]

    def make_plans(self, problems: Sequence[GemmProblem],
                   machine: MachineSpec, policy: str,
                   options: Mapping) -> list[GemmPlan]:
        overlap = bool(options.get("overlap", True))
        tile = options.get("tile")
        if tile is not None:
            return [self.plan_from_tile(p, machine, policy, tile,
                                        source="explicit", overlap=overlap)
                    for p in problems]
        # TileTuner's batched lattice search (deduped + memoised per machine).
        decisions = tune_batch([p.as_shape() for p in problems],
                               overlap=overlap, machine=machine)
        return [GemmPlan(
            problem=p, backend=self.name, machine=machine.name,
            selection=d.tile, cost=d.cost,
            provenance={"source": "search", "method": "tile_tuner",
                        "overlap": overlap, "policy": policy},
        ) for p, d in zip(problems, decisions)]

    def plan_from_tile(self, problem: GemmProblem, machine: MachineSpec,
                       policy: str, tile: TileConfig, *,
                       source: str = "manifest",
                       overlap: bool = True) -> GemmPlan:
        cost = estimate(problem.as_shape(), tile, machine)
        return GemmPlan(
            problem=problem, backend=self.name, machine=machine.name,
            selection=tile, cost=cost,
            provenance={"source": source, "method": "tile_tuner",
                        "overlap": overlap, "policy": policy},
        )


class PallasBackend(AnalyticTpuBackend):
    """analytic-tpu planning + Pallas execution (the full paper loop)."""

    name = "pallas"
    executable = True

    def execute(self, plan: GemmPlan, a, b, c=None, *,
                interpret: bool = False, force: bool = False):
        from repro.kernels import gemm as gemm_kernel
        from repro.kernels import ref

        p = plan.problem
        if a.shape != (p.m, p.k) or b.shape != (p.k, p.n):
            raise ValueError(
                f"operands {a.shape} @ {b.shape} do not match the planned "
                f"problem {p.m}x{p.n}x{p.k}")
        if not (_on_tpu() or interpret or force):
            # off-TPU the Pallas lowering is unavailable: same reference
            # fallback the kernels have always used on the dry-run path.
            return ref.gemm_ref(a, b, c)
        t = plan.selection
        bm, bn, bk = min(t.bm, p.m), min(t.bn, p.n), min(t.bk, p.k)
        tile = TileConfig(bm, bn, bk, t.order)
        with jax.named_scope("gemm.prep"):
            ap = _pad_to(a, (bm, bk))
            bp = _pad_to(b, (bk, bn))
            cp = None if c is None else _pad_to(c, (bm, bn))
        out = gemm_kernel.gemm(ap, bp, cp, tile=tile, interpret=interpret)
        with jax.named_scope("gemm.prep"):
            return out[:p.m, :p.n]


class ReferenceBackend(Backend):
    """Pure-jnp oracle: always correct, never tiled."""

    name = "reference"
    executable = True
    default_machine = "tpu-v5e"
    default_dtype = "bf16"

    def make_plan(self, problem: GemmProblem, machine: MachineSpec,
                  policy: str, options: Mapping) -> GemmPlan:
        shape = problem.as_shape()
        whole = TileConfig(problem.m, problem.n, problem.k,
                           GridOrder.K_INNER)
        return GemmPlan(
            problem=problem, backend=self.name, machine=machine.name,
            selection=None, cost=estimate(shape, whole, machine),
            provenance={"source": "closed-form", "method": "single-tile",
                        "policy": policy},
        )

    def execute(self, plan: GemmPlan, a, b, c=None, *,
                interpret: bool = False, force: bool = False):
        from repro.kernels import ref
        return ref.gemm_ref(a, b, c)


def register_builtin_backends() -> None:
    for cls in (AnalyticGap8Backend, AnalyticTpuBackend, PallasBackend,
                ReferenceBackend):
        register_backend(cls(), overwrite=True)
