"""Device time under the model's ``cast_weights`` scope (the weights' cast
to the compute dtype) per run of the decode program, in ms
(``lib.phases.Phases.per_step``); nothing where no operation ran under
the scope."""


def read(run):
    if run.trace is None or run.trace.phases is None:
        return None
    return run.trace.phases.per_step()["model.decode_weight_cast_ms"] or None
