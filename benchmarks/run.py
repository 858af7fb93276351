#!/usr/bin/env python3
"""One run of one cell of ``BENCHMARK.json``.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process per run. It needs as many TPU chips as the cell asks for (exit 2
otherwise, whatever ``JAX_PLATFORMS`` says), sets up and warms up from the
seed, measures for ``--seconds``, checks the answers outside the window and
prints ONE JSON object as the last line of stdout: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` with ``--trace 1``),
then ``checks``: each number compared beside its limit.
``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics.

Everything that belongs to one configuration, traffic mix, plane or per-layer
metric is a file of its own found by the name in ``BENCHMARK.json``; see
``benchmarks/README.md``.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()        # set-up counts from here

import argparse                        # noqa: E402
import contextlib                      # noqa: E402
import gc                              # noqa: E402
import importlib.util                  # noqa: E402
import json                            # noqa: E402
import os                              # noqa: E402
import shutil                          # noqa: E402
import sys                             # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

FAULTS = ("drop-ack", "flip-result")


def say(msg: str) -> None:
    print(msg, flush=True)


class Compiles:
    """XLA's compile activity, from JAX's own monitoring events
    (``chip_smoke.Compiles``): seconds compiling or loading from the
    persistent cache, cache hits, new cache entries, when the last one ended,
    and every one's end, seconds and function name in ``events``."""

    def __init__(self) -> None:
        import jax.monitoring

        self.secs = 0.0
        self.count = self.hits = self.misses = 0
        self.last = time.perf_counter()
        self.events: list[tuple[float, float, str]] = []
        jax.monitoring.register_event_duration_secs_listener(self._on_secs)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_secs(self, event: str, secs: float, **kw: object) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += secs
            self.count += 1
            self.last = time.perf_counter()
            self.events.append((self.last, secs, str(kw.get("fun_name", "?"))))

    def _on_event(self, event: str, **_: object) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def quiet_for(self) -> float:
        return time.perf_counter() - self.last

    def note(self) -> str:
        return (f"so far {self.count} programs compiled or loaded in "
                f"{self.secs:.1f}s ({self.hits} cache hits, {self.misses} "
                "new entries)")


class Context:
    """What a plane gets: the cell's files, the seed, and the harness's
    clocks, annotations and profiler switch."""

    def __init__(self, cell: dict, config: dict, traffic: dict, seed: int,
                 seconds: float, trace: bool, fault: str | None,
                 compiles: Compiles, trace_dir: str) -> None:
        self.cell, self.config, self.traffic = cell, config, traffic
        self.chips = cell["chips"]
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.fault, self.compiles, self.trace_dir = fault, compiles, trace_dir
        self.say = say
        self._window = None

    def annotate(self, phase: str):
        import jax

        return jax.profiler.TraceAnnotation("bench." + phase)

    def profile_start(self) -> None:
        import jax

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        self._window = self.annotate("window")
        self._window.__enter__()

    def profile_stop(self) -> None:
        import jax

        self._window.__exit__(None, None, None)
        jax.profiler.stop_trace()

    @staticmethod
    def gc_tune() -> None:
        """``bench._bench_gc_tune``: freeze the settled heap out of
        collection and raise gen0, so a gen-2 pass over the whole live
        server does not land inside the window."""
        gc.collect()
        gc.freeze()
        gc.set_threshold(100_000, 50, 100)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def find(kind: str, name: str, ext: str, data_root: str) -> str:
    """``<kind>/<name><ext>`` under ``data_root``, else under the
    benchmark's own directory."""
    for base in (data_root, HERE):
        path = os.path.join(base, kind, name + ext)
        if os.path.exists(path):
            return path
    raise SystemExit(f"benchmark: no {kind}/{name}{ext} under {data_root} "
                     f"or {HERE}")


_MODULES: dict[str, object] = {}


def load_module(kind: str, name: str, data_root: str):
    path = find(kind, name, ".py", data_root)
    if path not in _MODULES:
        spec = importlib.util.spec_from_file_location(
            f"benchmarks.{kind}.{name.replace('.', '_')}", path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        _MODULES[path] = module
    return _MODULES[path]


def load_cell(bench: dict, workload: str, data_root: str) -> tuple:
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"benchmark: no cell {workload!r}; there are "
                         f"{sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(os.path.join(ROOT, configs[cell["config"]]["file"]))
    traffic = load_json(find("traffic", cell["traffic"], ".json", data_root))
    return cell, config, traffic


def metrics_of(bench: dict, group: str, workload: str) -> list[dict]:
    return [m for m in bench[group]
            if workload in m.get("workloads", [workload])]


def plain(number):
    """A numpy scalar as the Python number ``json`` can write."""
    return number.item() if hasattr(number, "item") else number


def device_block(chips: int) -> dict:
    import jax

    devices = jax.devices()[:chips]
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": max((p for p in peaks if p is not None),
                                     default=None)}


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             fault: str | None = None, bench_file: str | None = None,
             data_root: str | None = None, require_tpu: bool = True
             ) -> tuple[int, dict | None]:
    """Drive one run; returns (exit code, the result line or None)."""
    bench = load_json(bench_file or os.path.join(ROOT, "BENCHMARK.json"))
    data_root = data_root or HERE
    cell, config, traffic = load_cell(bench, workload, data_root)

    import jax

    from copycat_tpu.utils.platform import enable_compilation_cache

    cache_dir = enable_compilation_cache()
    devices = jax.devices()
    say(f"benchmark: +{time.perf_counter() - T_PROCESS:.1f}s cell {workload} "
        f"seed={seed} seconds={seconds} "
        f"trace={int(trace)} fault={fault}; JAX {jax.__version__} found "
        f"{len(devices)} x {devices[0].platform} ({devices[0].device_kind}); "
        f"compile cache {cache_dir}")
    if require_tpu and (devices[0].platform != "tpu"
                        or len(devices) != cell["chips"]):
        print(f"benchmark: cell {workload} needs {cell['chips']} TPU "
              f"chip(s), JAX found {len(devices)} x {devices[0].platform}",
              file=sys.stderr, flush=True)
        return 2, None
    peaks = load_json(os.path.join(HERE, "peaks.json"))
    kind = devices[0].device_kind
    if require_tpu and kind not in peaks["devices"]:
        print(f"benchmark: no peaks for device kind {kind!r} in "
              "benchmarks/peaks.json", file=sys.stderr, flush=True)
        return 2, None

    compiles = Compiles()
    trace_dir = os.path.join(ROOT, ".bench_trace")
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
    ctx = Context(cell, config, traffic, seed, seconds, trace, fault,
                  compiles, trace_dir)
    plane = load_module("planes", traffic["plane"], data_root)
    gc_was = gc.get_threshold()
    try:
        res = plane.run(ctx)
    finally:                    # what Context.gc_tune changed
        gc.unfreeze()
        gc.set_threshold(*gc_was)

    setup_s = res["window_start"] - T_PROCESS
    say(f"benchmark: setup_s {setup_s:.3f} (process start to the window's "
        f"first instant); {compiles.note()}")
    for what, value, limit in res["checks"]:
        say(f"benchmark: check: {what}: {value} (limit {limit})")

    device = device_block(cell["chips"])
    line: dict = {"correct": bool(res["correct"]),
                  "attempted": int(res["attempted"]),
                  "failed": int(res["failed"]), "metrics": {},
                  "device": device}
    if not trace:
        values = {**res["end_to_end"], "setup_s": setup_s}
        for m in metrics_of(bench, "end_to_end", workload):
            line["metrics"][m["name"]] = {"value": values[m["name"]],
                                          "unit": m["unit"]}
    else:
        from benchmarks import trace_reduce

        devices_ev, notes = trace_reduce.load_xplane(
            trace_dir, "/device:TPU:" if require_tpu else "/host:CPU")
        reduced = trace_reduce.reduce_trace(devices_ev, notes)
        shutil.rmtree(trace_dir, ignore_errors=True)
        sources = {"clock": res["clock"], "spans": res["spans"],
                   "counters": res["counters"], "trace": reduced,
                   "peaks": peaks["devices"].get(kind, {})}
        for m in metrics_of(bench, "per_layer", workload):
            spec = load_json(find("layer_metrics", m["name"], ".json",
                                  data_root))
            reducer = load_module("reducers", spec["reducer"], data_root)
            value = reducer.reduce(sources, spec)
            if value is not None:
                line["metrics"][m["name"]] = {"value": value,
                                              "unit": m["unit"]}
        if reduced:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            line["breakdown"] = {"device_ops": reduced["device_ops"],
                                 "idle_gaps": reduced["idle_gaps"]}
    # each number compared beside its limit, the line's last key: what the
    # driver's record keeps of a run that was not correct
    line["checks"] = [[what[:160], plain(value), plain(limit)]
                      for what, value, limit in res["checks"]]
    return 0, line


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--fault", choices=FAULTS, default=None,
                        help="break the comparison's input in the harness; "
                             "such a run must print correct: false")
    args = parser.parse_args(argv)
    rc, line = run_cell(args.workload, args.seed, args.seconds,
                        bool(args.trace), args.fault)
    if line is not None:
        print(json.dumps(line), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
