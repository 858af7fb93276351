"""Share of the HBM-bandwidth roofline that the scan program reaches, in %.

Least time per round: every non-empty state leaf read once and written once
(2 x the state's bytes, from its shapes) over the device's peak bytes per
second (``benchmarks/peaks.json``). Measured time per round: the device time of
the program's modules in the profiler trace over the rounds they ran. The bytes
are a lower bound (temporaries and second passes are not counted), so the share
cannot pass 100%; it states that the bound is bandwidth, not compute.
"""


def least_seconds_per_round(state_bytes: int, peak_bytes_per_s: float) -> float:
    return 2.0 * state_bytes / peak_bytes_per_s


def reduce(sources: dict, spec: dict):
    clock, trace = sources["clock"], sources["trace"]
    runs = [d for name, _, d in trace.get("modules", [])
            if clock.get("program") and clock["program"] in name]
    if not runs or "hbm_bytes_per_s" not in sources["peaks"]:
        return None
    per_round = sum(runs) / 1e9 / (len(runs) * clock["rounds_per_dispatch"])
    least = least_seconds_per_round(clock["state_bytes"],
                                    sources["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / per_round
