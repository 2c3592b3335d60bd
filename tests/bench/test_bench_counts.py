"""Stage-1 kernel operations and bytes against a count by hand."""

import json

import pytest

from benchmarks.chip import counts, harness

B, N, ITERS = 4096, 16, 9
# Relaxation: 9 rounds of an add and a max per (b, u, v); closing: an add
# and a max per (b, v). Masked: one more add per (b, u, v).
PLAIN_OPS = 9 * 2 * 4096 * 16 * 16 + 2 * 4096 * 16  # 19,005,440
MASK_OPS = PLAIN_OPS + 4096 * 16 * 16  # 20,054,016
# float32 in: w (B n n), p (B n), extra (B) [, mask (B n n)]; out: lb (B).
PLAIN_BYTES = 4 * (4096 * 256 + 4096 * 16 + 4096 + 4096)  # 4,489,216
MASK_BYTES = PLAIN_BYTES + 4 * 4096 * 256  # 8,683,520


@pytest.mark.parametrize(
    "masked, ops, nbytes",
    [(False, PLAIN_OPS, PLAIN_BYTES), (True, MASK_OPS, MASK_BYTES)],
)
def test_stage1_counts_by_hand(masked, ops, nbytes):
    assert counts.stage1_ops(B, N, ITERS, masked) == ops
    assert counts.stage1_bytes(B, N, masked) == nbytes


def test_stage1_is_bound_by_bytes_on_v5e():
    peaks = json.loads((harness.CHIP_DIR / "peaks.json").read_text())
    peak = peaks["devices"]["TPU v5 lite"]
    assert peak == {"flops_per_s": 1.97e14, "bytes_per_s": 8.19e11}
    for masked in (False, True):
        t, bound = counts.min_seconds(
            counts.stage1_ops(B, N, ITERS, masked), counts.stage1_bytes(B, N, masked), peak
        )
        assert bound == "bytes"
        assert t == pytest.approx(counts.stage1_bytes(B, N, masked) / 8.19e11)
    assert counts.min_seconds(10**15, 1, peak)[1] == "ops"
