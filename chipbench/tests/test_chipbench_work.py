"""Required work against numbers worked by hand: one decode step and one
prefill of each configuration, at the v5e's peaks."""
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from lib import work  # noqa: E402
from lib.peaks import PEAKS, peaks_for  # noqa: E402
from lib.spec import ModelSpec  # noqa: E402

V5E = PEAKS["TPU v5 lite"]


def _spec(name):
    with open(BENCH / "configs" / f"{name}.json") as f:
        return ModelSpec(name, json.load(f))


QWEN = _spec("qwen2-1.5b")
#: granite-3.0-3b-a800m's published widths at 16 of its 32 layers (no cell
#: runs it yet: the program lacks the family's multipliers)
GRANITE = ModelSpec("granite-moe-3b-a800m", {
    "hidden_size": 1536, "intermediate_size": 512,
    "num_attention_heads": 24, "num_key_value_heads": 8, "head_dim": 64,
    "num_hidden_layers": 16, "num_local_experts": 40,
    "num_experts_per_tok": 8, "vocab_size": 49155,
    "tie_word_embeddings": True, "attention_bias": False})


def test_layer_params_by_hand():
    # qwen2: q and o 1536 x 1536, k and v 1536 x 256, MLP 3 x 1536 x 8960
    assert work.layer_matmul_params(QWEN) == \
        2359296 + 786432 + 2359296 + 41287680
    # granite: q and o 1536 x 1536, k and v 1536 x 512, router 1536 x 40,
    # 8 active experts of 3 x 1536 x 512
    assert work.layer_matmul_params(GRANITE) == \
        2359296 + 1572864 + 2359296 + 61440 + 18874368


def test_decode_flops_by_hand():
    # one token at position 99: 28 layers x (2 x 46792704 weights + 4 x 100
    # context x 12 heads x 128) + the head 2 x 1536 x 151936
    assert work.decode_flops(QWEN, [99]) == \
        28 * (2 * 46792704 + 4 * 100 * 1536) + 2 * 1536 * 151936
    assert work.decode_flops(GRANITE, [0, 0]) == \
        2 * (16 * (2 * 25227264 + 4 * 1 * 1536) + 2 * 1536 * 49155)


def test_prefill_flops_by_hand():
    # 200 positions, causal attention over 1 + 2 + ... + 200 = 20100
    assert work.prefill_flops(QWEN, 200) == \
        28 * (2 * 46792704 * 200 + 4 * 1536 * 20100)
    assert work.prefill_flops(GRANITE, 100) == \
        16 * (2 * 25227264 * 100 + 4 * 1536 * 5050)


def test_dense_gemm_least_time_by_hand():
    # decode, 16 slots: each of up and gate reads 16 x 1536 + 1536 x 8960
    # and writes 16 x 8960 bf16 values; memory-bound at 819 GB/s
    up = 2 * (16 * 1536 + 1536 * 8960 + 16 * 8960) / 819e9
    down = 2 * (16 * 8960 + 8960 * 1536 + 16 * 1536) / 819e9
    head = 2 * (16 * 1536 + 1536 * 151936 + 16 * 151936) / 819e9
    assert work.dense_gemm_least_s(QWEN, 16, 16, V5E) == \
        pytest.approx(28 * (2 * up + down) + head, rel=1e-12)
    # prefill of 1023 tokens: compute-bound, 2 x 1023 x 8960 x 1536 FLOPs
    # per projection at 197 TFLOP/s; no head (the first decode step has it)
    assert work.dense_gemm_least_s(QWEN, 1023, 0, V5E) == \
        pytest.approx(28 * 3 * 2 * 1023 * 8960 * 1536 / 197e12, rel=1e-12)
    # granite runs no dense MLP: only its head, over the real 49155 rows
    assert work.dense_gemms(GRANITE, 16, 16) == [(16, 49155, 1536)]


def test_grouped_least_time_by_hand():
    # decode, 16 tokens x 8 experts: 40 (1 - 0.8^16) experts read
    hit = 40 * (1 - 0.8 ** 16)
    assert work.experts_hit(GRANITE, 16) == pytest.approx(hit)
    nbytes = 2 * (hit * 3 * 1536 * 512 + 3 * 128 * (1536 + 512))
    assert work.grouped_least_s(GRANITE, 16, V5E) == \
        pytest.approx(16 * nbytes / 819e9, rel=1e-12)
    # prefill of 1000 tokens: 8000 routed rows, every expert hit; the rows'
    # inputs and outputs outweigh the compute (0.29 GB against 38 GFLOP)
    nbytes = 2 * (40 * 3 * 1536 * 512 + 3 * 8000 * (1536 + 512))
    assert 2 * 8000 * 3 * 1536 * 512 / 197e12 < nbytes / 819e9
    assert work.grouped_least_s(GRANITE, 1000, V5E) == \
        pytest.approx(16 * nbytes / 819e9, rel=1e-9)
    assert work.grouped_least_s(QWEN, 16, V5E) == 0.0


def test_unknown_chip_is_an_error():
    assert peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(LookupError):
        peaks_for("TPU v4")
