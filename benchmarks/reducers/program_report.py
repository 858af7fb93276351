"""A number of the program's own account of the window:
``copycat_tpu.utils.tracing.TRACER.report()``, frozen when the plane turned the
tracer off at the window's end (span aggregates over the whole window, the
timeline's shares, the delta of every registered counter).

``key`` is the path into the report, one list item per level (counter names
hold dots). ``per: "kop"`` or ``"op"`` divides by the operations acknowledged
in the window; ``over`` is the path of another value of the report to divide
by. ``None`` where the program has no report (the parent of the PR that added
it), a key is absent or a divisor is 0. It reads the program directly because
the ``served`` plane hands on one counter only.
"""


def lookup(report, path):
    for part in path:
        if not isinstance(report, dict) or part not in report:
            return None
        report = report[part]
    return report


def reduce(sources: dict, spec: dict):
    try:
        from copycat_tpu.utils.tracing import TRACER

        report = TRACER.report()
    except (ImportError, AttributeError):
        return None
    return reduce_report(report, sources, spec)


def reduce_report(report: dict, sources: dict, spec: dict):
    value = lookup(report, spec["key"])
    if value is None:
        return None
    if "over" in spec:
        divisor = lookup(report, spec["over"])
    elif "per" in spec:
        acked = sources["clock"].get("acked_ops")
        divisor = acked / {"kop": 1000.0, "op": 1.0}[spec["per"]] \
            if acked else None
    else:
        return float(value)
    if not divisor:
        return None
    return value / divisor
