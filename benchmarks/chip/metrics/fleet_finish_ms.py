"""Fleet driver: wall milliseconds per epoch of the program's
``fleet_finish`` spans: the host simulation of each instance's winning
assignment and the fleet's results."""


def read(red):
    t = red.span_seconds("fleet_finish")
    if not red.n_epochs or not t:
        return None
    return 1e3 * t / red.n_epochs
