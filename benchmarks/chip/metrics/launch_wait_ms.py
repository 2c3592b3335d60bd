"""Stage launches: host milliseconds per epoch spent in launch spans beyond
the device time of the two stage programs: dispatch, transfers and the wait
on the blocking sync."""

from benchmarks.chip.trace_reduce import STAGE1_PROGRAM, STAGE2_PROGRAM


def read(red):
    device = red.program_seconds(STAGE1_PROGRAM) + red.program_seconds(STAGE2_PROGRAM)
    launches = red.span_seconds("stage1_launch") + red.span_seconds("stage2_launch")
    if not red.n_epochs or not device or not launches:
        return None
    return 1e3 * (launches - device) / red.n_epochs
