"""Mixture-of-Experts block: top-k router, then a dropless token-major
expert layer on one device, or capacity-based dispatch under a mesh.

One device (no mesh, or a 1x1 mesh): every token of the call routes
together (B*S tokens).  The B*S*k assignments are sorted by expert, each
expert's group padded to the grouped kernel's row tile, and gate, up and
down run as one grouped GEMM each over those rows
(``kernels/grouped_gemm.py``); each token's output gathers its k rows back
and sums them weighted by the renormalised gates.  No capacity, no token
dropped: the layer computes what the published model does.  Named scopes
inside ``moe``: ``route`` (router, softmax, top-k, renormalise, sort),
``dispatch`` (the gather into expert order), ``combine`` (the gather back
and the gated sum); the kernel's own name, ``grouped_gemm``, marks the
GEMMs.

Under a mesh, dispatch is GShard/Switch-style with a fixed per-expert
capacity so all shapes are static, and -- crucially for SPMD -- it is
**per-sequence**: the scatter/gather that routes tokens into expert
buffers carries the batch dimension, so each data shard dispatches its own
sequences locally.  (The first implementation dispatched over the
flattened global token axis; the data-dependent scatter then defeated the
partitioner, which replicated the whole dispatch on every device -- ~500x
redundant compute and a 250 s collective term on granite train_4k.  See
EXPERIMENTS.md §Perf, iteration G1.)  Only these mesh paths, the pjit
einsum of ``apply_moe`` and ``apply_moe_ep``, have a capacity: a token
over it falls back to the residual path.  Their expert FFNs run as one
batched einsum over the expert dimension -- expert-parallel when
``n_experts`` divides the model axis (kimi-k2: 384/16), TP-inside-expert
otherwise (granite's 40 experts shard ``moe_d_ff`` instead; DESIGN.md §5).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import ops
from repro.kernels.grouped_gemm import padded_rows, row_tile
from repro.models.common import MeshInfo, dense_init


def padded_experts(cfg, mesh: MeshInfo) -> int:
    """Physical expert count: padded up to a model-axis multiple so the
    expert dim shards and the EP all-to-all path applies (granite's 40 -> 48
    on a 16-way axis); the logical count on one device, where the
    token-major layer pads rows, not experts.  Dead experts get -inf router
    logits, so routing is exactly the logical model's (EXPERIMENTS.md §Perf
    iteration G3)."""
    e, m = cfg.n_experts, mesh.model
    if m > 1 and e % m:
        return m * ((e + m - 1) // m)
    return e


def init_moe(key, cfg, mesh: MeshInfo, dtype):
    d, f = cfg.d_model, cfg.moe_d_ff
    e0 = cfg.n_experts
    e = padded_experts(cfg, mesh)
    e_ax = mesh.shard_if(e)
    f_ax = mesh.shard_if(f) if e_ax is None else None   # TP fallback
    fsdp = mesh.fsdp_if(d)
    ks = jax.random.split(key, 4)

    def pad_e(p, axis):
        """Draw logical-shape weights, zero-pad the expert dim — identical
        logical parameters regardless of mesh (dead experts stay zero: they
        receive no tokens, hence no gradient)."""
        if e == e0:
            return p
        pads = [(0, 0)] * p.value.ndim
        pads[axis] = (0, e - e0)
        from repro.models.common import Param
        return Param(jnp.pad(p.value, pads), p.spec)

    return {
        "router": pad_e(dense_init(ks[0], d, (d, e0), P(fsdp, None),
                                   jnp.float32), 1),
        "w_gate": pad_e(dense_init(ks[1], d, (e0, d, f),
                                   P(e_ax, fsdp, f_ax), dtype), 0),
        "w_up": pad_e(dense_init(ks[2], d, (e0, d, f),
                                 P(e_ax, fsdp, f_ax), dtype), 0),
        "w_down": pad_e(dense_init(ks[3], f, (e0, f, d),
                                   P(e_ax, f_ax, fsdp), dtype), 0),
    }


def _masked_router_logits(params, x, cfg):
    """Router logits over physical experts; padded tail masked to -inf."""
    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                        params["router"])
    e_phys = logits.shape[-1]
    if e_phys > cfg.n_experts:
        mask = jnp.arange(e_phys) >= cfg.n_experts
        logits = jnp.where(mask, -1e9, logits)
    return logits


def _capacity(tokens: int, cfg) -> int:
    c = int(cfg.capacity_factor * tokens * cfg.experts_per_token / cfg.n_experts)
    return max(8, (c + 7) // 8 * 8)


def _constrain(val, spec: P):
    """with_sharding_constraint under the mesh path (the scatter's output
    sharding does not propagate through vmapped scatters; without the
    constraint the SPMD partitioner replicates the dispatch buffers —
    EXPERIMENTS.md §Perf iteration G2)."""
    return jax.lax.with_sharding_constraint(val, spec)


def _route(params, x, cfg):
    """(gates (B,S,k) renormalised, experts (B,S,k), aux loss); the router
    in f32 for stability."""
    e, k = params["router"].shape[-1], cfg.experts_per_token
    logits = _masked_router_logits(params, x, cfg)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, k)          # (B,S,k)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(axis=-1, keepdims=True), 1e-9)
    # load-balancing auxiliary loss (per sequence, then mean)
    me = probs.mean(axis=1)                                  # (B,E)
    ce = jax.nn.one_hot(expert_idx[:, :, 0], e,
                        dtype=jnp.float32).mean(axis=1)      # (B,E)
    aux = cfg.router_aux_coef * e * jnp.mean(jnp.sum(me * ce, axis=-1))
    return gate_vals, expert_idx, aux


def token_major_layout(experts, n_experts: int, block_m: int):
    """Rows in expert order for the flat assignments ``experts`` (A,):
    (the row of each assignment, each expert's group size padded to a
    multiple of ``block_m``).  Expert ``e``'s group starts where the padded
    groups before it end, so every row lies below
    ``padded_rows(A, n_experts, block_m)``."""
    counts = jnp.bincount(experts, length=n_experts)
    padded = (counts + block_m - 1) // block_m * block_m
    order = jnp.argsort(experts, stable=True)
    sorted_e = experts[order]
    first = jnp.cumsum(counts) - counts          # in the sorted assignments
    start = jnp.cumsum(padded) - padded          # in the padded rows
    dest = start[sorted_e] + jnp.arange(experts.shape[0]) - first[sorted_e]
    row_of = jnp.zeros_like(experts).at[order].set(dest)
    return row_of, padded.astype(jnp.int32)


def _apply_moe_token_major(params, x, cfg):
    """The dropless single-device layer (module docstring)."""
    b, s, d = x.shape
    e, k = params["router"].shape[-1], cfg.experts_per_token
    tokens = b * s
    block_m = row_tile(tokens * k, e)
    rows = padded_rows(tokens * k, e, block_m)
    with jax.named_scope("route"):
        gate_vals, expert_idx, aux = _route(params, x, cfg)
        row_of, group_sizes = token_major_layout(
            expert_idx.reshape(-1), e, block_m)
    with jax.named_scope("dispatch"):
        # the token (and whether there is one) of every padded row
        src = jnp.zeros((rows,), jnp.int32).at[row_of].set(
            jnp.arange(tokens * k, dtype=jnp.int32) // k)
        live = jnp.zeros((rows,), bool).at[row_of].set(True)
        xs = jnp.where(live[:, None], x.reshape(tokens, d)[src], 0)
    g = ops.grouped_gemm(xs, params["w_gate"], group_sizes, block_m=block_m)
    u = ops.grouped_gemm(xs, params["w_up"], group_sizes, block_m=block_m)
    h = jax.nn.silu(g) * u
    out = ops.grouped_gemm(h, params["w_down"], group_sizes, block_m=block_m)
    with jax.named_scope("combine"):
        picked = out[row_of].reshape(tokens, k, d).astype(jnp.float32)
        y = (picked * gate_vals.reshape(tokens, k, 1)).sum(axis=1)
    return y.reshape(b, s, d).astype(x.dtype), aux


def apply_moe(params, x, cfg, mesh: MeshInfo | None = None):
    """x: (B, S, D) -> (y, aux_loss).  One device: the dropless token-major
    layer; under a mesh: per-sequence capacity, overflow to the residual."""
    if mesh is None or (mesh.data == 1 and mesh.model == 1):
        return _apply_moe_token_major(params, x, cfg)
    b, s, d = x.shape
    e, k = params["router"].shape[-1], cfg.experts_per_token
    cap = _capacity(s, cfg)
    gate_vals, expert_idx, aux = _route(params, x, cfg)

    # --- per-sequence dispatch (batched scatter: local per data shard) ----
    flat_e = expert_idx.reshape(b, s * k)                    # (B, S*k)
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)      # (B, S*k, E)
    pos_all = jnp.cumsum(onehot, axis=1) - 1
    pos = jnp.take_along_axis(pos_all, flat_e[..., None],
                              axis=2)[..., 0]                # (B, S*k)
    keep = pos < cap
    safe_pos = jnp.where(keep, pos, 0)
    tok_idx = jnp.repeat(jnp.arange(s), k)                   # (S*k,)

    def scatter_one(xt, fe, sp, kp):
        src = jnp.where(kp[:, None], xt[tok_idx], 0).astype(xt.dtype)
        return jnp.zeros((e, cap, d), xt.dtype).at[fe, sp].add(src)

    buf = jax.vmap(scatter_one)(x, flat_e, safe_pos, keep)   # (B,E,cap,D)
    e_ax = mesh.shard_if(e)
    buf = _constrain(buf, P(mesh.dp(), e_ax, None, None))

    # --- expert FFN (SwiGLU), batched over experts ------------------------
    # the einsum form: the SPMD partitioner sees one op to shard over the
    # expert axis.
    g = jnp.einsum("becd,edf->becf", buf, params["w_gate"])
    u = jnp.einsum("becd,edf->becf", buf, params["w_up"])
    h = jax.nn.silu(g) * u
    out_buf = jnp.einsum("becf,efd->becd", h, params["w_down"])
    out_buf = _constrain(out_buf, P(mesh.dp(), e_ax, None, None))

    # --- combine (batched gather + gate weighting) ------------------------
    # The whole combine stays in bf16: the (S*k, D) gathered tensor crosses
    # the model axis (partial sums over expert shards), and in f32 its
    # forward+cotangent all-reduces dominated kimi-k2's collective term
    # (EXPERIMENTS.md §Perf iteration K1: 2x payload reduction).  The
    # gate-weighted sum has <= top_k terms per token — bf16-safe.
    def gather_one(ob, fe, sp, kp, gv):
        eo = ob[fe, sp]                                      # (S*k, D) bf16
        gvb = gv.reshape(-1).astype(ob.dtype)
        contrib = jnp.where(kp[:, None], eo, 0) * gvb[:, None]
        return jnp.zeros((s, d), ob.dtype).at[tok_idx].add(contrib)

    y = jax.vmap(gather_one)(out_buf, flat_e, safe_pos, keep, gate_vals)
    y = _constrain(y, P(mesh.dp(), None, None))
    return y.astype(x.dtype), aux


# ---------------------------------------------------------------------------
# True expert-parallel path (shard_map + all_to_all)
# ---------------------------------------------------------------------------


def ep_applicable(cfg, mesh: MeshInfo | None, seq_len: int) -> bool:
    if mesh is None or mesh.model <= 1 or seq_len % mesh.model:
        return False
    return padded_experts(cfg, mesh) % mesh.model == 0


def apply_moe_ep(params, x, cfg, mesh: MeshInfo):
    """Expert-parallel MoE via ``shard_map``: sequence-split routing + two
    ``all_to_all`` exchanges (dispatch / return).

    Under plain pjit the cross-expert-shard combine lowers to all-reduces of
    the full (B, S*k, D) activation (f32-promoted on top): kimi-k2's
    dominant collective.  Here each (data, model) device routes its own
    S/model-axis token slice, ships expert inputs directly to their owner
    shard and back — payload = tokens x top_k x D in bf16, no reduction op
    at all.  EXPERIMENTS.md §Perf iteration K2 (~7x on kimi's collective
    term).  Capacity is enforced per sequence-chunk (S/M tokens): this
    path, like ``apply_moe`` under a mesh, drops what overflows it.
    """
    b, s, d = x.shape
    e, k = padded_experts(cfg, mesh), cfg.experts_per_token
    m_ax = mesh.model_axis
    mm = mesh.model
    e_loc = e // mm
    s_loc = s // mm
    cap = _capacity(s_loc, cfg)
    dp = mesh.dp()
    tok_idx = jnp.repeat(jnp.arange(s_loc), k)

    def body(router, w_gate, w_up, w_down, xs):
        # xs: (B_loc, S/M, D) — this device's sequence slice.
        bl = xs.shape[0]
        gate_vals, expert_idx, aux = _route({"router": router}, xs, cfg)
        aux = jax.lax.pmean(jax.lax.pmean(aux, m_ax), dp)

        flat_e = expert_idx.reshape(bl, s_loc * k)
        onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)
        pos = jnp.take_along_axis(jnp.cumsum(onehot, axis=1) - 1,
                                  flat_e[..., None], axis=2)[..., 0]
        keep = pos < cap
        safe_pos = jnp.where(keep, pos, 0)

        def scatter_one(xt, fe, sp, kp):
            src = jnp.where(kp[:, None], xt[tok_idx], 0).astype(xt.dtype)
            return jnp.zeros((e, cap, d), xt.dtype).at[fe, sp].add(src)

        buf = jax.vmap(scatter_one)(xs, flat_e, safe_pos, keep)  # (B,E,cap,D)
        # dispatch: experts go to their owner shard; sources stack on axis 1
        buf = buf.reshape(bl, mm, e_loc, cap, d)
        buf = jax.lax.all_to_all(buf, m_ax, split_axis=1, concat_axis=1,
                                 tiled=False)                  # (B,M_src,E_loc,cap,D)

        g = jnp.einsum("bmecd,edf->bmecf", buf, w_gate)
        u = jnp.einsum("bmecd,edf->bmecf", buf, w_up)
        h = jax.nn.silu(g) * u
        ob = jnp.einsum("bmecf,efd->bmecd", h, w_down)
        # return trip
        ob = jax.lax.all_to_all(ob, m_ax, split_axis=1, concat_axis=1,
                                tiled=False)
        ob = ob.reshape(bl, e, cap, d)

        def gather_one(o1, fe, sp, kp, gv):
            eo = o1[fe, sp]
            gvb = gv.reshape(-1).astype(o1.dtype)
            contrib = jnp.where(kp[:, None], eo, 0) * gvb[:, None]
            return jnp.zeros((s_loc, d), o1.dtype).at[tok_idx].add(contrib)

        y = jax.vmap(gather_one)(ob, flat_e, safe_pos, keep, gate_vals)
        return y.astype(xs.dtype), aux

    from repro.runtime.sharding import ambient_mesh
    mesh_ctx = ambient_mesh()
    if mesh_ctx is None:
        raise RuntimeError(
            "apply_moe_ep needs an ambient mesh; wrap the call in "
            "`with repro.runtime.sharding.use_mesh(mesh):`")
    fn = jax.shard_map(
        body,
        mesh=mesh_ctx,
        in_specs=(P(), P(mesh.model_axis, None, None),
                  P(mesh.model_axis, None, None),
                  P(mesh.model_axis, None, None),
                  P(dp, mesh.model_axis, None)),
        out_specs=(P(dp, mesh.model_axis, None), P()),
        check_vma=False,
    )
    y, aux = fn(params["router"], params["w_gate"], params["w_up"],
                params["w_down"], x)
    return y, aux
