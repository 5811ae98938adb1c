"""The decode step's crossing between host and device.

Once a step admits nothing, ``ServingEngine.step()`` moves its inputs to the
device in one explicit ``jax.device_put`` and its tokens back in one explicit
``jax.device_get``.  An implicit transfer -- a Python int written into a
device array, ``int()`` of a device element, ``jnp.array`` of a list -- is a
device program per slot, and ``jax.transfer_guard("disallow")`` refuses it.
"""
import jax
import pytest

from repro.configs import get_config
from repro.models.common import HOST_MESH, split_params
from repro.models.model import LM
from repro.serving.engine import Request, ServingEngine


@pytest.fixture(scope="module", params=["qwen2-1.5b", "zamba2-1.2b"])
def drained(request):
    """An engine whose decode-only steps all ran under the guard.

    The first step admits both requests (prefill and insert are outside the
    guard) and compiles the decode; then both slots decode, then one."""
    lm = LM(get_config(request.param, smoke=True), HOST_MESH)
    values, _ = split_params(lm.init(jax.random.key(0)))
    eng = ServingEngine(lm, values, max_batch=3, max_len=64)
    eng.submit(Request(rid=0, prompt=[5, 6, 7, 8], max_new_tokens=5))
    eng.submit(Request(rid=1, prompt=[1, 2, 3], max_new_tokens=2))
    eng.step()
    active_sets = []
    with jax.transfer_guard("disallow"):
        while active := [i for i, r in enumerate(eng.slot_req)
                         if r is not None]:
            active_sets.append(active)
            eng.step()
    return eng, active_sets


def test_decode_only_step_makes_no_implicit_transfer(drained):
    eng, active_sets = drained
    assert active_sets[0] == [0, 1] and active_sets[-1] == [0]
    done = {r.rid: r.generated for r in eng.finished}
    assert sorted(done) == [0, 1]
    assert [len(done[0]), len(done[1])] == [5, 2]
    assert all(type(t) is int for g in done.values() for t in g)


def test_decode_compiles_once_across_active_sets(drained):
    eng, active_sets = drained
    assert len({tuple(a) for a in active_sets}) > 1
    assert eng._decode._cache_size() == 1
