#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, no children. The last line of standard output is the result;
earlier lines are ``{"info": ...}`` objects. Without a TPU of a kind in the
benchmark's peaks table, or with another number of chips than the cell asks
for, it exits non-zero and prints no result. ``--rehearse`` alone accepts
another device and swaps in each file's tiny ``rehearse`` sizes: its lines
say so and name the device, and nothing it prints is a measurement.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from benchmark import peaks, readers, spec  # noqa: E402

TRACE_DIR = ROOT / ".bench_trace"


def info(kind: str, **fields) -> None:
    print(json.dumps({"info": kind, **fields}, default=float), flush=True)


class Context:
    """What a traffic kind's driver needs from the harness."""

    def __init__(self, args, cell, device, watch):
        import jax

        self.seed, self.seconds = args.seed, float(args.seconds)
        self.trace, self.rehearse = bool(args.trace), args.rehearse
        self.model, self.traffic, self.family = cell.model, cell.traffic, cell.family
        self.device, self.watch = device, watch
        self.on_tpu = device.platform == "tpu"
        # Seeds run past 2**31: fold the high bits in, jax keys take 32.
        self.key = jax.random.fold_in(
            jax.random.key(args.seed >> 31), args.seed & 0x7FFFFFFF
        )
        self.info = info
        self.phases: dict[str, float] = {}
        self.setup_s = None
        self.setup_watch = None
        self.window_compiles = None
        self._last = T_PROCESS

    def phase(self, name: str) -> None:
        now = time.perf_counter()
        self.phases[name] = now - self._last
        self._last = now

    def window_opens(self, t_start: float) -> None:
        """Set-up ends at the first instant of the window. From here on the
        name of whatever compiles is kept: there should be nothing."""
        import jax

        self.setup_s = t_start - T_PROCESS
        self.setup_watch = self.watch.report()
        self.compiled_after_setup: list[str] = []

        def on_duration(event, seconds, fun_name=None, **_):
            if event.endswith("backend_compile_duration"):
                self.compiled_after_setup.append(str(fun_name))

        jax.monitoring.register_event_duration_secs_listener(on_duration)

    def window_closes(self) -> int:
        after = self.watch.report()
        self.window_compiles = (
            after["backend_compiles"] - self.setup_watch["backend_compiles"]
        )
        self.window_traces = after["traces"] - self.setup_watch["traces"]
        return self.window_compiles

    def start_trace(self) -> None:
        import jax

        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0      # the slice is about the device
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=options)

    def stop_trace(self) -> None:
        import jax

        jax.profiler.stop_trace()

    def peak_bytes(self) -> int:
        import jax

        stats = [d.memory_stats() or {} for d in jax.devices()]
        return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)


def check_device(chips: int, rehearse: bool):
    """The device this run is about, or exit non-zero with nothing on stdout."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    if rehearse:
        return dev, None
    if dev.platform != "tpu":
        sys.exit(f"benchmark: no TPU (platform {dev.platform!r}); --rehearse runs elsewhere")
    try:
        table = peaks.peaks_of(dev.device_kind)
    except KeyError as e:
        sys.exit(f"benchmark: {e}")
    if len(devices) != chips:
        sys.exit(f"benchmark: this cell needs {chips} chip(s), found {len(devices)}")
    return dev, table


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    with open(ROOT / "BENCHMARK.json") as f:
        bench = json.load(f)
    if args.seconds is None:
        args.seconds = bench["run_seconds"]
    cell = spec.load_cell(
        args.workload, rehearse=args.rehearse, readers=readers.READERS, bench=bench
    )

    import jax

    from learning_jax_sharding_tpu.telemetry import CompileWatch
    from learning_jax_sharding_tpu.utils.compile_cache import place_compile_cache

    cache_dir = place_compile_cache()
    device, table = check_device(cell.chips, args.rehearse)
    watch = CompileWatch().start()
    ctx = Context(args, cell, device, watch)
    ctx.phase("imports_and_device")
    info(
        "start", workload=cell.name, seed=args.seed, seconds=args.seconds,
        trace=args.trace, rehearse=args.rehearse, compile_cache_dir=cache_dir,
        platform=device.platform, device_kind=device.device_kind,
        device_count=len(jax.devices()), jax=jax.__version__,
        note="REHEARSAL: tiny sizes, not a measurement" if args.rehearse else None,
    )

    kind = cell.traffic["kind"]
    if kind == "train":
        from benchmark import train as driver
    else:
        from benchmark import serve as driver
    result = driver.run(cell, ctx)
    watch.stop()

    obs = result["observed"]
    obs.update(setup_watch=ctx.setup_watch, peaks=table, trace=None)
    device_line = {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(jax.devices()), "memory_peak_bytes": result["peak_bytes"],
    }
    line = {
        "correct": result["correct"], "attempted": result["attempted"],
        "failed": result["failed"],
    }
    end_to_end = {**result["e2e"], "setup_s": ctx.setup_s}
    info(
        "setup", setup_s=ctx.setup_s, phases=ctx.phases,
        trace_s=ctx.setup_watch["trace_seconds"], traces=ctx.setup_watch["traces"],
        backend_compile_s=ctx.setup_watch["backend_compile_seconds"],
        backend_compiles=ctx.setup_watch["backend_compiles"],
        cache_hits=ctx.setup_watch["cache_hits"],
        cache_misses=ctx.setup_watch["cache_misses"],
        window_backend_compiles=ctx.window_compiles,
        window_traces=ctx.window_traces,
        compiled_after_setup=ctx.compiled_after_setup,
    )
    breakdown = None
    if args.trace:
        from benchmark import trace as tr

        t0 = time.perf_counter()
        xplane = tr.find_xplane(str(TRACE_DIR))
        try:
            red = tr.reduce_trace(xplane)
        except ValueError:
            if not args.rehearse:        # a rehearsal's CPU has no device plane
                raise
            red = None
        if red is not None:
            obs["trace"] = red
            device_line.update(busy_s=red.busy_s, window_s=red.window_s)
            breakdown = {
                "device_ops": tr.top_ops(red.planes[0]),
                "idle_gaps": tr.gaps_by_host_event(red.planes[0], red.host_threads),
            }
            info(
                "trace", reduce_s=time.perf_counter() - t0,
                device_events=sum(len(p.ops) for p in red.planes),
                modules=tr.module_summary(red.planes[0]),
                ops=tr.top_ops(red.planes[0], 30),
                end_to_end_in_traced_run=end_to_end,
            )
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        metrics = {}
        for m in cell.per_layer:
            if table is None and m["source"] == "device_trace":
                continue                     # a rehearsal has no device numbers
            value = readers.READERS[m["file"]["reader"]](m["file"].get("params", {}), obs)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        metrics = {}
        for m in cell.end_to_end:
            if m["name"] not in end_to_end:
                sys.exit(f"benchmark: {cell.name} produced no {m['name']}")
            metrics[m["name"]] = {"value": end_to_end[m["name"]], "unit": m["unit"]}
    line.update(metrics=metrics, device=device_line)
    if args.rehearse:
        line["rehearsal"] = "tiny sizes on " + device.platform + ": not a measurement"
    if breakdown is not None:
        line["breakdown"] = breakdown
    print(json.dumps(line, default=float), flush=True)


if __name__ == "__main__":
    main()
