"""Stage-1 bound: device milliseconds per epoch of the stage-1 program
(``jit__fleet_lb_device``, which holds the Pallas kernel), from the trace."""

from benchmarks.chip.trace_reduce import STAGE1_PROGRAM


def read(red):
    t = red.program_seconds(STAGE1_PROGRAM)
    if not red.n_epochs or not t:
        return None
    return 1e3 * t / red.n_epochs
