"""Device milliseconds per call of the jitted decode step, in the traced
part of the window."""

MODULE = "jit_decode_step"


def read(run):
    d = run["trace"].module_durations(MODULE) if run.get("trace") else []
    return 1e3 * sum(d) / len(d) if d else None
