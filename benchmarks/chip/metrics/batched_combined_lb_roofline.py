"""Stage-1 bound: the Pallas ``batched_combined_lb`` kernel's share of its
roofline, in percent: the least time its launches could take on this chip
(the larger of operations over peak FLOP/s and bytes over peak bandwidth,
from ``counts.py``) over the kernel's device time in the trace. The kernel's
device events are matched to the recorded launch shapes in launch order;
where their numbers differ, nothing is read."""

from benchmarks.chip import counts


def read(red):
    events = red.kernel_events()
    if not events or len(events) != len(red.stage1_shapes):
        return None
    least = 0.0
    for B, n, n_iters, masked in red.stage1_shapes:
        ops = counts.stage1_ops(B, n, n_iters, masked)
        nbytes = counts.stage1_bytes(B, n, masked)
        least += counts.min_seconds(ops, nbytes, red.peak)[0]
    return 100.0 * least / sum(d for _n, _s, d in events)
