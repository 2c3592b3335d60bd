"""Fleet driver: host milliseconds per epoch inside ``schedule_fleet`` that
are not a device launch (candidate generation, survivor compaction, packing,
the portfolio), from the program's spans."""


def read(red):
    fleet = red.span_seconds("schedule_fleet")
    if not red.n_epochs or not fleet:
        return None
    launches = red.span_seconds("stage1_launch") + red.span_seconds("stage2_launch")
    return 1e3 * (fleet - launches) / red.n_epochs
