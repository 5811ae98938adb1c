"""Required work: operations and bytes a layer needs, counted from the
configuration and the token counts, never from a kernel's padded, bucketed
or capacity shapes.  So a program change that removes padding or a kernel
cannot push a share past 100%.

``cfg`` is the run configuration (``lib.spec.ModelSpec``): the published
keys ``hidden_size``, ``intermediate_size``, ``num_attention_heads``,
``num_key_value_heads``, ``head_dim``, ``vocab_size``, and for a mixture of
experts ``num_local_experts``, ``num_experts_per_tok`` and the expert width
``intermediate_size``; ``num_hidden_layers`` as run.
"""
from __future__ import annotations

BF16 = 2        # bytes per element of the served compute dtype


def gemm_least_s(m: int, n: int, k: int, peaks: dict) -> float:
    """Least time of one bf16 ``(m, k) @ (k, n)``: the larger of its
    operations over peak FLOP/s and its bytes (both operands read once, the
    result written once) over peak HBM bandwidth."""
    flops = 2.0 * m * n * k
    nbytes = BF16 * (m * k + k * n + m * n)
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])


def dense_gemms(cfg, m: int, logits_m: int) -> list[tuple[int, int, int]]:
    """(m, n, k) of the planned dense GEMMs of one forward over ``m``
    tokens, of which ``logits_m`` go through the logits head: the gated
    MLP's up, gate and down projections in every dense layer, and the head
    over the real vocabulary."""
    d, f = cfg.hidden_size, cfg.intermediate_size
    out = []
    if not cfg.is_moe and m:
        out += [(m, f, d), (m, f, d), (m, d, f)] * cfg.num_hidden_layers
    if logits_m:
        out.append((logits_m, cfg.vocab_size, d))
    return out


def dense_gemm_least_s(cfg, m: int, logits_m: int, peaks: dict) -> float:
    return sum(gemm_least_s(*g, peaks) for g in dense_gemms(cfg, m, logits_m))


def experts_hit(cfg, tokens: int) -> float:
    """Expected number of distinct experts that ``tokens`` tokens route to
    under uniform routing: E (1 - (1 - k/E)^tokens)."""
    e, k = cfg.num_local_experts, cfg.num_experts_per_tok
    return e * (1.0 - (1.0 - k / e) ** tokens)


def grouped_least_s(cfg, tokens: int, peaks: dict) -> float:
    """Least time of the expert GEMMs (gate, up, down) of every layer for
    ``tokens`` tokens: tokens x top-k routed rows of operations, and the
    weights of the experts that receive a token plus the routed rows'
    inputs and outputs in bytes."""
    if not cfg.is_moe or not tokens:
        return 0.0
    d, f, k = cfg.hidden_size, cfg.intermediate_size, cfg.num_experts_per_tok
    rows = tokens * k
    flops = 2.0 * rows * 3 * d * f
    # gate and up read d and write f per row, down reads f and writes d
    nbytes = BF16 * (experts_hit(cfg, tokens) * 3 * d * f
                     + 3 * rows * (d + f))
    per_layer = max(flops / peaks["bf16_flops_per_s"],
                    nbytes / peaks["hbm_bytes_per_s"])
    return per_layer * cfg.num_hidden_layers


def layer_matmul_params(cfg) -> int:
    """Weights one token multiplies through in one layer (active experts
    only)."""
    d, hd = cfg.hidden_size, cfg.head_dim
    h, hkv = cfg.num_attention_heads, cfg.num_key_value_heads
    attn = d * h * hd + 2 * d * hkv * hd + h * hd * d
    if cfg.is_moe:
        e, k = cfg.num_local_experts, cfg.num_experts_per_tok
        return attn + d * e + k * 3 * d * cfg.intermediate_size
    return attn + 3 * d * cfg.intermediate_size


def position_flops(cfg, context: int) -> float:
    """Model FLOPs of one position through every layer, attending to
    ``context`` positions (itself included); no logits head."""
    attn = 4.0 * context * cfg.num_attention_heads * cfg.head_dim
    return cfg.num_hidden_layers * (2.0 * layer_matmul_params(cfg) + attn)


def logits_flops(cfg) -> float:
    return 2.0 * cfg.hidden_size * cfg.vocab_size


def prefill_flops(cfg, n: int) -> float:
    """Positions 0..n-1 of a prompt, causal; no logits."""
    per = 2.0 * layer_matmul_params(cfg) * n
    attn = 4.0 * cfg.num_attention_heads * cfg.head_dim * n * (n + 1) / 2
    return cfg.num_hidden_layers * (per + attn)


def decode_flops(cfg, positions: list[int]) -> float:
    """One decode step over slots at the given positions (0-based): each
    attends to position + 1 entries and goes through the logits head."""
    return sum(position_flops(cfg, p + 1) + logits_flops(cfg)
               for p in positions)
