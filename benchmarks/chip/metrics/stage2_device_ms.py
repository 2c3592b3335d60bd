"""Stage-2 evaluator: device milliseconds per epoch of the stage-2 program
(``jit__scan_evaluate``), from the trace."""

from benchmarks.chip.trace_reduce import STAGE2_PROGRAM


def read(red):
    t = red.program_seconds(STAGE2_PROGRAM)
    if not red.n_epochs or not t:
        return None
    return 1e3 * t / red.n_epochs
