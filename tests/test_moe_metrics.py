"""The benchmark's three expert-layer readers on a made-up trace reduction:
``moe.decode_ms`` and ``moe.decode_routing_share`` read the decode
program's ``moe`` scope and its ``route``, ``dispatch`` and ``combine``
parts; ``moe.grouped_gemm_roofline`` finds the kernel by its name,
whatever the rank of its result, and stays silent where it did not run."""
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

BENCH = Path(__file__).resolve().parents[1] / "chipbench"
sys.path.insert(0, str(BENCH))

from lib import work  # noqa: E402
from lib.measure import reader  # noqa: E402
from lib.peaks import PEAKS  # noqa: E402
from lib.phases import Phases  # noqa: E402
from lib.spec import ModelSpec  # noqa: E402
from lib.trace import Reduction  # noqa: E402

METRICS = BENCH / "metrics"
GRANITE = ModelSpec("g", {"hidden_size": 1536, "intermediate_size": 512,
                          "num_local_experts": 40, "num_experts_per_tok": 8,
                          "num_hidden_layers": 16})
V5E = PEAKS["TPU v5 lite"]
CALL = ('custom-call(bf16[736,1536]{1,0} %a, bf16[40,1536,512]{2,1,0} %b), '
        'custom_call_target="tpu_custom_call"')


def _run(ops=None, scopes=None, prefills=(), steps=()):
    phases = None
    if scopes is not None:
        phases = Phases(window_s=10.0, busy_s=9.0, spans={}, idle_self={},
                        idle_in={}, modules={"jit__decode_impl": [100, 2.0]},
                        scopes={"jit__decode_impl": scopes})
    trace = Reduction(window_s=10.0, busy_s=9.0, modules={}, ops=ops or {},
                      idle_by_label={}, devices=1, phases=phases)
    steps = [SimpleNamespace(tokens=t) for t in steps]
    return SimpleNamespace(trace=trace, spec=GRANITE, peaks=V5E,
                           window_prefills=lambda: list(prefills),
                           window_steps=lambda: steps)


def test_moe_decode_ms_and_routing_share():
    scopes = {"attention": [1600, 0.3], "moe": [1600, 0.4],
              "moe/route": [1600, 0.1], "moe/dispatch": [1600, 0.05],
              "moe/combine": [1600, 0.05], "moe/grouped_gemm": [4800, 0.6],
              "cast_weights": [100, 0.5]}
    run = _run(scopes=scopes)
    assert reader(METRICS, "moe.decode_ms")(run) == pytest.approx(12.0)
    assert reader(METRICS, "moe.decode_routing_share")(run) == \
        pytest.approx(100 * 0.2 / 1.2)


@pytest.mark.parametrize("scopes", [None, {"attention": [10, 0.1]}])
def test_moe_readers_silent_without_the_scope(scopes):
    run = _run(scopes=scopes)
    assert reader(METRICS, "moe.decode_ms")(run) is None
    assert reader(METRICS, "moe.decode_routing_share")(run) is None


def test_grouped_gemm_roofline_finds_the_kernel_by_name():
    ops = {f"%grouped_gemm.3 = bf16[736,512]{{1,0}} {CALL}": [2400, 0.2],
           f"%grouped_gemm = bf16[13312,1536]{{1,0}} {CALL}": [48, 0.05],
           f"%gemm_k_inner.4 = bf16[16,49408]{{1,0}} {CALL}": [50, 0.5],
           "%grouped_gemm.9 = bf16[8,8]{1,0} fusion(%x)": [50, 0.5]}
    run = _run(ops=ops, prefills=[201], steps=[5] * 50)
    least = work.grouped_least_s(GRANITE, 200, V5E) + \
        50 * work.grouped_least_s(GRANITE, 5, V5E)
    got = reader(METRICS, "moe.grouped_gemm_roofline")(run)
    assert got == pytest.approx(100 * least / 0.25)
    assert 0 < got < 100


def test_grouped_gemm_roofline_silent_where_the_kernel_did_not_run():
    ops = {f"%gemm_k_inner.4 = bf16[16,9216]{{1,0}} {CALL}": [50, 0.5]}
    assert reader(METRICS, "moe.grouped_gemm_roofline")(
        _run(ops=ops, steps=[5])) is None
    assert reader(METRICS, "moe.grouped_gemm_roofline")(
        SimpleNamespace(trace=None)) is None
