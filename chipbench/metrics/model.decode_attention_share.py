"""The share of the decode program's operation time (``_decode_impl``, the
loops that hold other operations left out) under the model's ``attention``
scope, in % (``lib.phases.Phases.per_step``); nothing where no operation
ran under the scope."""


def read(run):
    if run.trace is None or run.trace.phases is None:
        return None
    return run.trace.phases.per_step()["model.decode_attention_share"] or None
