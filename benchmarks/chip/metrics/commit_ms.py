"""Epoch loop: mean wall milliseconds per epoch of the arbitrate-and-commit
stage, from the program's ``arbitrate_and_commit`` spans."""


def read(red):
    t = red.span_seconds("arbitrate_and_commit")
    if not red.n_epochs or not t:
        return None
    return 1e3 * t / red.n_epochs
