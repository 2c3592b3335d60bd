"""Fleet driver: wall milliseconds per epoch of the program's
``fleet_enumerate`` spans: building each instance's search state (candidate
enumeration or sampling, seed pools, the refinement portfolio), garbage
collections inside them included."""


def read(red):
    t = red.span_seconds("fleet_enumerate")
    if not red.n_epochs or not t:
        return None
    return 1e3 * t / red.n_epochs
