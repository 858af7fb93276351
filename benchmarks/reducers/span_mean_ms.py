"""Mean duration, in ms, of the program's spans named ``key`` that were in the
tracer's ring when the window ended."""


def reduce(sources: dict, spec: dict):
    durations = sources["spans"].get(spec["key"])
    if not durations:
        return None
    return sum(durations) / len(durations)
