"""The window's delta of the program's counter ``key`` over the plane's own
count ``over`` of the same window (its drives, its operations)."""


def reduce(sources: dict, spec: dict):
    delta = sources["counters"].get(spec["key"])
    count = sources["clock"].get(spec["over"])
    if delta is None or not count:
        return None
    return delta / count
