"""The share of the decode program's device time under the model's ``moe``
scope that its ``route``, ``dispatch`` and ``combine`` scopes take (the
router and the sort, the gather into expert order, the gather back and the
gated sum: everything of the expert layer but its grouped GEMMs), in %;
nothing where no operation ran under ``moe``."""
DECODE = r"_decode_impl"
ROUTING = ("route", "dispatch", "combine")


def read(run):
    if run.trace is None or run.trace.phases is None:
        return None
    ph = run.trace.phases
    moe = ph.scope_s(DECODE, "moe")
    if not moe:
        return None
    return 100.0 * sum(ph.scope_s(DECODE, s) for s in ROUTING) / moe
