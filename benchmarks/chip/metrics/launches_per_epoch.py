"""Stage launches: device launches per epoch, from the program's
``stage1_launches`` and ``stage2_launches`` counters. Each launch ends in a
blocking device-to-host copy."""


def read(red):
    n = red.counters.get("stage1_launches", 0) + red.counters.get("stage2_launches", 0)
    if not red.n_epochs or not n:
        return None
    return n / red.n_epochs
