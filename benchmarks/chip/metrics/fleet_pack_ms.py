"""Fleet driver: wall milliseconds per epoch of the program's ``fleet_pack``
spans: filling the candidate-row and instance-id arrays of each launch."""


def read(red):
    t = red.span_seconds("fleet_pack")
    if not red.n_epochs or not t:
        return None
    return 1e3 * t / red.n_epochs
