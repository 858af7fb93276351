"""Share of the HBM-bandwidth roofline that the map lookup reaches, in %.

Least bytes: what the traced map operations have to move whatever implements
the lookup. A command reads its key's bucket (the bucket's rows of key, value,
deadline and live, ``bucket_bytes``) on every replica and writes it back; a
query reads it on the leader's replica. Never the table: an operation that is
not about the whole map has no business with more than its bucket. Time: the
device time of the operations that implement the lookup, if the trace shows
them by the name ``key`` (a kernel of that name); else the device time of the
round and query programs' modules (``clock["programs"]``), and then every
module run that is a round also has to read and write the state outside the
table once (``other_state_bytes``, as ``hbm_roofline`` counts a state), which
is added to the least bytes.

It cannot pass 100% by construction: the bytes are a lower bound on what the
timed operations move (each counted byte has to cross the HBM interface at
least once inside them, temporaries and second passes are not counted, and the
operations counted are those acknowledged strictly inside the traced seconds,
which the traced programs evaluated), and the time is all of theirs. A low
share says that the lookup is bound by what an index costs, not by bandwidth.
"""


def least_bytes(commands: int, queries: int, replicas: int,
                bucket_bytes: int) -> int:
    return (2 * replicas * commands + queries) * bucket_bytes


def reduce(sources: dict, spec: dict):
    clock, trace = sources["clock"], sources["trace"]
    peak = sources["peaks"].get("hbm_bytes_per_s")
    commands, queries = (clock.get("traced_commands"),
                         clock.get("traced_queries"))
    if not trace or not peak or commands is None or not commands + queries:
        return None
    least = least_bytes(commands, queries, clock["replicas"],
                        clock["bucket_bytes"])
    kernel_s = sum(s for name, s in trace.get("device_ops", [])
                   if spec["key"] in name)
    if kernel_s:
        return 100.0 * least / peak / kernel_s
    runs = [(name, d) for name, _, d in trace.get("modules", [])
            if any(p in name for p in clock["programs"])]
    seconds = sum(d for _, d in runs) / 1e9
    if not seconds:
        return None
    rounds = sum(1 for name, _ in runs if clock["round_program"] in name)
    least += 2 * clock["other_state_bytes"] * rounds
    return 100.0 * least / peak / seconds
