"""Host runtime: milliseconds per epoch of the Python garbage collector's
pauses, from the program's ``gc`` spans (one per collection while the
tracer has a span open). Zero where the tracer counts collections
(``gc_collections``) and none ran."""


def read(red):
    if not red.n_epochs or "gc_collections" not in red.counters:
        return None
    return 1e3 * red.span_seconds("gc") / red.n_epochs
