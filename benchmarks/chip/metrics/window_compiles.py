"""Stage launches: XLA executables built while the traced serve ran, each a
compile or a load from the persistent cache, from the program's
``xla_compiles`` counter. A warm serve builds none."""


def read(red):
    n = red.counters.get("xla_compiles")
    if n is None:
        return None
    return float(n)
