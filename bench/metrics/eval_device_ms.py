"""Device self time under the program's ``tracker.evaluate`` scope (the
population evaluation, ``core/objective``) per frame completed in the
traced window, in ms; None where the trace holds no such scope."""

SCOPE = "tracker.evaluate"


def read(run):
    if run.scopes is None or run.frames == 0:
        return None
    seconds = dict(run.scopes.device_scopes).get(SCOPE)
    return None if seconds is None else 1e3 * seconds / run.frames
