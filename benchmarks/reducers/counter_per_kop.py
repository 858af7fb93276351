"""The window's delta of the program's counter ``key`` per 1,000 operations
acknowledged in the window."""


def reduce(sources: dict, spec: dict):
    delta = sources["counters"].get(spec["key"])
    acked = sources["clock"].get("acked_ops")
    if delta is None or not acked:
        return None
    return delta / (acked / 1000.0)
