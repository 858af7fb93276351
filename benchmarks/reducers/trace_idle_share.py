"""Share of the traced window, in %, in which no operation ran on the device:
1 - union of device-operation intervals over the window."""


def reduce(sources: dict, spec: dict):
    return sources["trace"].get("idle_share_pct")
