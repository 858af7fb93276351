"""A number the harness took on its own clock: ``sources["clock"][key]``."""


def reduce(sources: dict, spec: dict):
    return sources["clock"].get(spec["key"])
