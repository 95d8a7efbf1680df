"""Device self time under the program's ``tracker.collide`` scope (the
two-hand interpenetration penalty, ``core/objective.interpenetration``)
per frame completed in the traced window, in ms; None where the trace
holds no such scope, as with one hand."""

SCOPE = "tracker.collide"


def read(run):
    if run.scopes is None or run.frames == 0:
        return None
    seconds = dict(run.scopes.device_scopes).get(SCOPE)
    return None if seconds is None else 1e3 * seconds / run.frames
