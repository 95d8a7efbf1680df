"""Device idle, on the host's clock, while a client is in the host part
of ``Tracker.step``: its key split, its dispatch or its wait for the
score (the program's spans ``tracker.key_split``, ``tracker.dispatch``,
``tracker.wait``), per frame completed in the traced window, in ms; None
where the trace holds none of those spans. With several clients, a gap
counts where any client is in one of them."""

SPANS = ("tracker.key_split", "tracker.dispatch", "tracker.wait")


def _in_step(label: str) -> bool:
    return any(part.rsplit(">", 1)[-1] in SPANS for part in label.split("+"))


def read(run):
    if run.scopes is None or run.frames == 0:
        return None
    gaps = [seconds for label, seconds in run.scopes.program_gaps if _in_step(label)]
    return 1e3 * sum(gaps) / run.frames if gaps else None
