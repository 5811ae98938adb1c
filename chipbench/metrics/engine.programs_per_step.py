"""XLA programs run on the device in the window per engine step
(``serve.step`` spans): the decode, and per admitted request its prefill,
its insert and the small programs that build its inputs
(``lib.phases.Phases.per_step``)."""


def read(run):
    if run.trace is None or run.trace.phases is None:
        return None
    return run.trace.phases.per_step()["engine.programs_per_step"]
