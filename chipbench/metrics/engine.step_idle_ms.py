"""Device idle time inside the engine's ``serve.step`` spans, in ms per step
that decoded (``serve.decode`` spans): what the host's own work in a step
leaves the chip waiting, read from the program's spans on the trace's clock
(``lib.phases.Phases.per_step``)."""


def read(run):
    if run.trace is None or run.trace.phases is None:
        return None
    return run.trace.phases.per_step()["engine.step_idle_ms"]
