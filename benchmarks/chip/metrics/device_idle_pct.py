"""Device: percent of the traced window in which no operation ran on the
chip (one minus the union of device-operation intervals over the window)."""

from benchmarks.chip.trace_reduce import union_seconds


def read(red):
    if red.window_s <= 0 or not red.ops:
        return None
    return 100.0 * (1.0 - union_seconds(red.ops) / red.window_s)
