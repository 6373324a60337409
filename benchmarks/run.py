#!/usr/bin/env python3
"""The benchmark's one command:

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s>
                              --trace <0|1> [--rehearse]

One run of one cell of ``BENCHMARK.json`` in a new process: set up, warm
up, measure for ``--seconds``, check the outputs against the plain
reference, and print one JSON object as the last line of standard output
(``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` and, in a
traced run, ``breakdown``). ``--trace 0`` reports the cell's end-to-end
metrics with no telemetry and no profiler; ``--trace 1`` reports its
per-layer metrics from the program's ``--metrics-dir`` and a device trace
of a few steady seconds.

Without a TPU, with fewer chips than the cell asks for, on a
``device_kind`` the peaks table lacks, or where the cell's traffic keeps
its volumes in memory and ``/dev/shm`` has no room for them
(:mod:`cfbench.workdir`), it exits non-zero and prints no result.
``--rehearse`` runs the same code at the tiny sizes the configuration and
traffic files give under ``rehearse``, on CPU devices, and reports
``platform: cpu``: a rehearsal of the control flow, never a measurement.
"""
import time

T0 = time.time()   # set-up is counted from here

import argparse      # noqa: E402
import json          # noqa: E402
import os            # noqa: E402
import sys           # noqa: E402
import threading     # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(BENCH_DIR)
CHECKOUT_WORK = os.path.join(BENCH_DIR, ".work")
sys.path.insert(0, BENCH_DIR)
sys.path.insert(0, CHECKOUT)

from cfbench import catalog, peaks, trace, workdir  # noqa: E402


class Context:
    """What a driver gets: the cell's data, the run's arguments, a work
    directory, and the two services that need the chip's one process."""

    def __init__(self, args, bench):
        self.t0 = T0
        self.cell = catalog.cell(bench, args.workload)
        self.config = catalog.config_of(bench, self.cell)
        self.traffic = catalog.load_json(
            "traffic", self.cell["traffic"] + ".json")
        if args.rehearse:
            self.config = {**self.config, **self.config.get("rehearse", {})}
            self.traffic = {**self.traffic,
                            **self.traffic.get("rehearse", {})}
        self.seed, self.seconds = args.seed, args.seconds
        self.trace, self.rehearse = bool(args.trace), args.rehearse
        self.device = None     # as JAX reports it, once it is asked
        self.work, self.work_note = workdir.place(
            self.traffic, self.cell["name"], args.rehearse, CHECKOUT_WORK)
        self.metrics_dir = os.path.join(self.work, "metrics")
        self.trace_dir = os.path.join(self.work, "trace")
        self.profiler_error = None
        self._memory_peaks = None

    def start_profiler_thread(self, window, spec) -> threading.Thread:
        """Trace ``spec['seconds']`` steady seconds, ``start_after_s``
        into the window, from a thread of its own: stopping a trace takes
        seconds, and the caller has a queue to feed."""
        import jax

        def capture():
            try:
                time.sleep(max(0.0, window[0] + float(spec["start_after_s"])
                               - time.time()))
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
                options.host_tracer_level = 2
                jax.profiler.start_trace(self.trace_dir,
                                         profiler_options=options)
                time.sleep(float(spec["seconds"]))
                jax.profiler.stop_trace()
            except BaseException as exc:    # raised by main() after the run
                self.profiler_error = exc

        thread = threading.Thread(target=capture, name="bench-profiler",
                                  daemon=True)
        thread.start()
        return thread

    def memory_peaks(self) -> dict:
        """The devices' peaks, read once: a driver asks when its window
        has closed and before the plain reference runs on the chip (a
        process's peak never falls again, and the reference's programs
        reserve scratch of their own); ``main`` asks again and gets the
        same."""
        if self._memory_peaks is None:
            self._memory_peaks = memory_peaks(int(self.cell["chips"]))
        return self._memory_peaks

    @staticmethod
    def resolve_args(args: list) -> list:
        """Arguments that name a file of the checkout, made absolute."""
        return [os.path.join(CHECKOUT, a) if isinstance(a, str)
                and os.path.exists(os.path.join(CHECKOUT, a)) and "/" in a
                else a for a in args]

    def engine_params(self):
        """The parameter tree the program's engine makes from its seed for
        this configuration: what the plain reference is given."""
        from chunkflow_tpu.inference.engines import create_flax_engine

        engine = self.config["engine"]
        return create_flax_engine(
            self.resolve_args([engine.get("model_path") or ""])[0], None,
            tuple(self.config["patch"]),
            self.config["model"]["in_channels"],
            self.config["model"]["out_channels"],
            dtype=engine.get("dtype", "float32"),
            model_variant=engine.get("model_variant", "parity")).params


def describe_devices(chips: int, rehearse: bool) -> dict:
    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if rehearse:
        return device
    if device["platform"] != "tpu":
        raise SystemExit(
            f"benchmarks: needs a TPU; jax.devices()[0].platform is "
            f"{device['platform']!r} (JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS')!r}). No result.")
    if device["count"] < chips:
        raise SystemExit(
            f"benchmarks: the cell needs {chips} chip(s), JAX sees "
            f"{device['count']}. No result.")
    peaks.peaks_for(device["kind"])
    return device


def place_compile_cache() -> None:
    """Where ``JAX_COMPILATION_CACHE_DIR`` says, else
    ``<checkout>/.jax_cache``: the same rule the program follows, so both
    share one cache at a path that never moves."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(CHECKOUT, ".jax_cache"))


def memory_peaks(chips: int) -> dict:
    """Per device, the two peaks ``memory_stats()`` keeps: of the buffers
    in use, and of what the runtime reserved for the loaded programs'
    scratch. A v5e counts the two apart (reserved + allocated + available
    = the chip's HBM in the trace's allocator events), and a convnet's
    scratch is most of its footprint. The reservation is held from a
    program's load to its release (``bytes_reserved`` still reads its peak
    after the window, PERF.md), so it stands while the buffers peak."""
    import jax

    in_use, reserved = [], []
    for device in jax.devices()[:chips]:
        stats = device.memory_stats() or {}
        in_use.append(int(stats.get("peak_bytes_in_use", 0)))
        reserved.append(int(stats.get("peak_bytes_reserved", 0)))
        print(f"note: {device}: peak in use {in_use[-1] / 1e9:.3f} GB, "
              f"reserved now {stats.get('bytes_reserved', 0) / 1e9:.3f} GB, "
              f"peak reserved {reserved[-1] / 1e9:.3f} GB of "
              f"{stats.get('bytes_limit', 0) / 1e9:.3f} GB", file=sys.stderr)
    return {"hbm_peak_in_use_bytes": in_use,
            "hbm_peak_reserved_bytes": reserved}


def reduce_metrics(entries: list, directory: str, record) -> dict:
    """Each metric's definition file names its reducer and arguments; a
    reducer that finds nothing to read returns None and the metric is
    left out of the line."""
    out = {}
    for entry in entries:
        definition = catalog.load_json(directory, entry["name"] + ".json")
        reducer = catalog.load_module("reducers", definition["reducer"])
        value = reducer.reduce(record, **definition.get("args", {}))
        if value is not None:
            out[entry["name"]] = {"value": float(value),
                                  "unit": entry["unit"]}
    return out


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=None)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearse", action="store_true")
    args = parser.parse_args()

    bench = catalog.benchmark()
    if args.seconds is None:
        args.seconds = float(bench["run_seconds"])
    chips = int(catalog.cell(bench, args.workload)["chips"])
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={max(chips, 1)}"
        ).strip()
        # CPU entries stay out of <checkout>/.jax_cache
        os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(
            CHECKOUT_WORK, "rehearsal-cache"))

    # before JAX is asked for a device: a cell whose volumes find no room
    # in memory ends here, as one that finds no chip ends below
    ctx = Context(args, bench)
    print(f"note: {ctx.work_note}", file=sys.stderr)
    ctx.device = device = describe_devices(chips, args.rehearse)
    place_compile_cache()
    for name, value in (ctx.traffic.get("env") or {}).items():
        os.environ[name] = str(value)
    driver = catalog.load_module("drivers", ctx.traffic["kind"])
    workdir.create(ctx.work, CHECKOUT_WORK)
    try:
        record = driver.run(ctx)
        if ctx.profiler_error is not None:
            raise ctx.profiler_error
        record.client.update(ctx.memory_peaks())
        for note in record.notes:
            print(f"note: {note}", file=sys.stderr)
        breakdown = None
        if ctx.trace:
            from cfbench import program

            events = program.read_events(ctx.metrics_dir)
            record.spans = program.read_spans(events)
            record.counters = program.read_counters(events)
            record.programs = program.read_programs(ctx.metrics_dir)
            # one answer to "is this op a convolution" for every reducer:
            # what the programs list inside it, where they say
            record.trace = trace.file_by_contents(
                trace.load_run_trace(ctx.trace_dir, chips), record.programs)
            print(f"note: {trace.filing_note(record.trace['filing'])}",
                  file=sys.stderr)
            breakdown = trace.breakdown(record.trace, record.programs)
        group, directory = (("per_layer", "layer_metrics") if ctx.trace
                            else ("end_to_end", "end_to_end"))
        metrics = reduce_metrics(
            catalog.metrics_of(bench, ctx.cell["name"], group),
            directory, record)
    finally:
        workdir.remove(ctx.work)

    # the HBM the process occupied on the fullest chip: buffers and the
    # programs' scratch, which this runtime counts apart
    device_out = dict(device, memory_peak_bytes=max(
        used + held for used, held in zip(
            record.client["hbm_peak_in_use_bytes"],
            record.client["hbm_peak_reserved_bytes"])))
    if ctx.trace:
        busy = trace.busy_seconds(record.trace)
        device_out["busy_s"] = sum(busy) / len(busy)
        device_out["window_s"] = record.trace["window_s"]
    line = {"correct": bool(record.correct),
            "attempted": int(record.attempted),
            "failed": int(record.failed),
            "metrics": metrics, "device": device_out}
    if breakdown is not None:
        line["breakdown"] = breakdown
    # each number compared beside its limit: last in the line, and the
    # run's last words on standard error
    line["checks"] = record.checks
    for name, check in record.checks.items():
        print(f"check: {name} {check['value']!r} limit {check['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    sys.stdout.flush()
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
