"""Stage launches: wall milliseconds per epoch of the program's
``stage1_dispatch`` and ``stage2_dispatch`` spans: the host-to-device copies
of each launch's inputs and the call that enqueues its program, up to the
blocking sync."""


def read(red):
    t = red.span_seconds("stage1_dispatch") + red.span_seconds("stage2_dispatch")
    if not red.n_epochs or not t:
        return None
    return 1e3 * t / red.n_epochs
