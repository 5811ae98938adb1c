"""Device time under the model's ``moe`` scope (the expert layers: routing,
dispatch, the grouped GEMMs, combine) per run of the decode program, in ms
(``lib.phases.Phases.scope_s``); nothing where no operation ran under the
scope."""
import re

DECODE = r"_decode_impl"


def read(run):
    if run.trace is None or run.trace.phases is None:
        return None
    ph = run.trace.phases
    runs = sum(c for m, (c, _) in ph.modules.items() if re.search(DECODE, m))
    sec = ph.scope_s(DECODE, "moe")
    return 1e3 * sec / runs if runs and sec else None
