"""Fleet driver: wall milliseconds per epoch of the program's
``fleet_rounds`` spans: the host work of the lockstep rounds between
launches (next chunks, pruning and buffering survivors, applying scores,
the portfolio's proposals)."""


def read(red):
    t = red.span_seconds("fleet_rounds")
    if not red.n_epochs or not t:
        return None
    return 1e3 * t / red.n_epochs
