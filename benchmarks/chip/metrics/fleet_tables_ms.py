"""Fleet driver: wall milliseconds per epoch of the program's
``fleet_tables`` spans: the fleet's size bucket, the stacked stage-1 and
stage-2 tables and their copies to the device."""


def read(red):
    t = red.span_seconds("fleet_tables")
    if not red.n_epochs or not t:
        return None
    return 1e3 * t / red.n_epochs
