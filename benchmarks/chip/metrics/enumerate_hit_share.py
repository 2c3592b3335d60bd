"""Fleet driver: the share of canonical rack enumerations served from the
process-wide memo of ``enumerate_assignments`` rather than built, from the
program's ``enumerate_cache_hits`` and ``enumerate_cache_misses`` counters."""


def read(red):
    hits = red.counters.get("enumerate_cache_hits", 0.0)
    total = hits + red.counters.get("enumerate_cache_misses", 0.0)
    if not total:
        return None
    return 100.0 * hits / total
