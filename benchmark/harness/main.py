"""Run one cell once: load, set up, check, measure, print one line.

    --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Exit codes: 0 a result line was printed (``correct`` may be false); 2 no
accelerator, or fewer chips than the cell asks for; 3 the program under test
is not importable from this checkout; 4 the line the run would have printed
breaks the contract; 1 anything else. Only exit code 0 prints a result line.
"""
import argparse
import contextlib
import json
import os
import shutil
import sys
import time

from . import compiles as compiles_mod
from . import contract, spec as spec_mod, trace as trace_mod

TRACE_SECONDS = 4.0   # a traced run measures at most this long: traces are
#                       large and tracing slows the host
TRACE_DIR = ".bench_trace"  # inside the checkout, git-ignored


def say(msg):
    sys.stderr.write("[bench] %s\n" % msg)
    sys.stderr.flush()


def steady_allocator():
    """Tell glibc never to give freed heap back to the kernel, and to serve
    blocks up to 32 MB from the heap. A deployment setting of this process,
    not an option of the program: a host loop that allocates and frees tens
    of megabytes per step (the decode step stages 42 MB per token) otherwise
    runs at one of two speeds, by the luck of whether those blocks end up on
    top of the heap, where glibc trims and re-faults them every step
    (PERF.md section 6, PR 22: 509 or 566 tokens/s, 103 or 86 ms a
    dispatch, and 557-576 in five runs of five with this set). No-op where
    libc is not glibc."""
    import ctypes

    try:
        mallopt = ctypes.CDLL("libc.so.6").mallopt
    except (OSError, AttributeError):
        return
    mallopt(-1, 1 << 30)    # M_TRIM_THRESHOLD
    mallopt(-3, 32 << 20)   # M_MMAP_THRESHOLD (its largest allowed value)


def parse(argv):
    ap = argparse.ArgumentParser(prog="benchmark/run.py",
                                 description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="debug here without a chip: tiny configuration, "
                         "Pallas interpreted, labelled, no result line")
    ap.add_argument("--break-reference", action="store_true",
                    help="perturb the reference's weights: the run must "
                         "then report correct: false")
    ap.add_argument("--set", action="append", default=[], metavar="K=V",
                    help="override config.<path>=<json> or "
                         "traffic.<path>=<json> (benchmark/sweep.py); the "
                         "line then carries \"overrides\"")
    ap.add_argument("--keep-trace", default=None, metavar="DIR",
                    help="copy the traced run's .xplane.pb here")
    return ap.parse_args(argv)


def apply_overrides(pairs, targets):
    """``config.serving.lanes=32`` sets targets["config"]["serving"]["lanes"]."""
    done = {}
    for pair in pairs:
        path, _, raw = pair.partition("=")
        keys = path.split(".")
        node = targets[keys[0]]
        for key in keys[1:-1]:
            node = node[key]
        if keys[-1] not in node:
            raise spec_mod.SpecError("--set %s: no such key" % path)
        node[keys[-1]] = done[path] = json.loads(raw)
    return done


class Run:
    """What a driver and the metric readers see of one run."""

    def __init__(self, args, spec, cell, config, traffic, devices, peaks,
                 compiles, t_start):
        self.spec, self.cell = spec, cell
        self.config, self.traffic = config, traffic
        self.seed = args.seed
        self.trace = bool(args.trace)
        self.break_reference = args.break_reference
        self.seconds = min(args.seconds, TRACE_SECONDS) if self.trace \
            else args.seconds
        self.devices = devices          # the cell's chips, and no others
        self.chips = len(devices)
        self.peaks = peaks              # None in a rehearsal
        self.compiles = compiles
        self.t_start = t_start
        self.setup_s = None
        self.window = None              # (t0, t1) on time.perf_counter
        self.obs = None                 # what the driver observed
        self.end_to_end = {}            # {name: value}, readers may chain
        self.trace_summary = None       # trace.reduce(...) of a traced run
        self.compiles_setup = self.compiles_window = None
        self.counters_setup = self.counters_window = None
        self.spans = []                 # program spans inside the window
        self.memory_peak_bytes = None   # peak on the fullest chip
        self.notes = {"setup_phases": []}  # for people and for sweep.py
        self._t_mark = t_start
        self._trace_dir = os.path.join(spec.root, TRACE_DIR, cell["name"])
        self._stack = contextlib.ExitStack()

    # ---- for drivers
    def say(self, msg):
        say(msg)

    def mark(self, phase, at=None):
        """Name the part of set-up that just ended (now, or at ``at``); its
        seconds since the previous mark go into the line's notes."""
        now = time.perf_counter() if at is None else at
        self.notes["setup_phases"].append([phase, now - self._t_mark])
        self._t_mark = now

    def memory_peak(self):
        """The peak on the fullest of the cell's chips so far. A driver
        reads it before its reference runs and returns it as
        ``memory_peak_bytes``."""
        return memory_peak(self.devices)

    def reference(self):
        """The configuration's plain reference (``reference/<name>.py``)."""
        return self.spec.module("reference", self.config["reference"])

    def annotate(self, name):
        """A ``bench.*`` span on the profiler's clock (traced runs only)."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def open_window(self):
        """Set-up ends here. Returns the window's start on perf_counter."""
        from . import program

        self.compiles_setup = self.compiles.snap()
        self.counters_setup = program.counters()
        if self.trace:
            import jax

            program.clear_spans()
            shutil.rmtree(self._trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0   # our spans, not every call
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self._trace_dir, profiler_options=opts)
            self._stack.enter_context(
                jax.profiler.TraceAnnotation(trace_mod.WINDOW_SPAN))
        t0 = time.perf_counter()
        self.setup_s = t0 - self.t_start
        self.window = (t0, None)
        return t0

    def close_window(self):
        from . import program

        t1 = time.perf_counter()
        self.window = (self.window[0], t1)
        self.compiles_window = compiles_mod.since(self.compiles_setup,
                                                  self.compiles.snap())
        before, now = self.counters_setup, program.counters()
        self.counters_window = {k: now[k] - before.get(k, 0) for k in now
                                if now[k] != before.get(k, 0)}
        self._stack.close()
        if self.trace:
            import jax

            jax.profiler.stop_trace()
            self.spans = [s for s in program.spans()
                          if self.window[0] <= s[1] <= t1]
        return t1

    # ---- for the harness
    def reduce_trace(self, keep=None):
        path = trace_mod.find(self._trace_dir)
        if path is None:
            return
        self.trace_summary = trace_mod.reduce(trace_mod.read(path))
        if keep:
            os.makedirs(keep, exist_ok=True)
            shutil.copy(path, os.path.join(
                keep, self.cell["name"] + ".xplane.pb"))
        shutil.rmtree(self._trace_dir, ignore_errors=True)


def memory_peak(devices):
    """The peak on the fullest chip so far, from the runtime's counters:
    live buffers at their peak plus the most it ever reserved for a running
    program's temporaries. On the TPU runtime the two are counted apart
    (PERF.md section 6, PR 22: a ResNet-50 step read 0.5 GB "in use" and
    5.1 GB "reserved", the compiler's own figure for its temporaries)."""
    def peak(d):
        stats = d.memory_stats() or {}
        return stats.get("peak_bytes_in_use", 0) + \
            stats.get("peak_bytes_reserved", 0)
    return int(max(peak(d) for d in devices))


def read_metrics(run, kind, folder):
    """{name: {"value", "unit"}} from each declared metric's reader; a
    reader that finds nothing to read returns None and is left out."""
    out = {}
    for m in run.spec.metrics(kind, run.cell["name"]):
        value = run.spec.module(folder, m["name"]).read(run)
        if value is None:
            say("%s: nothing to read" % m["name"])
            continue
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
        if kind == "end_to_end":
            run.end_to_end[m["name"]] = float(value)
    return out


def main(argv, t_start=None, root=None):
    """``root`` is the checkout whose BENCHMARK.json is run (the tests run a
    temporary copy)."""
    t_start = time.perf_counter() if t_start is None else t_start
    args = parse(argv)
    steady_allocator()
    if args.rehearse_cpu and "jax" not in sys.modules:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                   " --xla_force_host_platform_device_count=4")
    spec = spec_mod.Spec(root or spec_mod.ROOT)
    cell = spec.cell(args.workload)
    config = spec.config(cell, tiny=args.rehearse_cpu)
    traffic = spec.traffic(cell, tiny=args.rehearse_cpu)
    overrides = apply_overrides(args.set, {"config": config,
                                           "traffic": traffic})

    import jax

    devices = jax.devices()
    t_devices = time.perf_counter()
    want = "cpu" if args.rehearse_cpu else "tpu"
    if devices[0].platform != want or len(devices) < cell["chips"]:
        say("%s needs %d %s device(s); JAX found %d x %s. This benchmark "
            "runs on the chip only: no result."
            % (cell["name"], cell["chips"], want, len(devices),
               devices[0].platform))
        return 2
    devices = devices[:cell["chips"]]
    if args.rehearse_cpu:
        print("*** REHEARSAL on the CPU at a tiny size: this debugs the "
              "benchmark and is NOT a result ***", flush=True)
        peaks = None
        # a rehearsal leaves nothing in the checkout's compile cache
        jax.config.update("jax_enable_compilation_cache", False)
    else:
        peaks = spec_mod.peaks(devices[0].device_kind, spec.bench_dir)
        # every program this process compiles goes into the persistent
        # cache, however small: a second run of the cell compiles nothing
        # (PR 21: ~260 programs under JAX's 1 s threshold, 57 s every run)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compiles = compiles_mod.Compiles()
    try:
        driver = spec.module("drivers", traffic["driver"])
        from . import program

        if args.trace:
            program.trace_on()
    except ImportError as exc:
        say("the program under test cannot be imported from this checkout "
            "(%s): no result." % exc)
        return 3

    run = Run(args, spec, cell, config, traffic, devices, peaks, compiles,
              t_start)
    run.mark("import jax, jax.devices()", at=t_devices)
    run.mark("import the driver and the program")
    run.obs = obs = driver.run(run)
    if run.window is None or run.window[1] is None:
        raise RuntimeError("driver %r never opened and closed its window"
                           % traffic["driver"])

    line = {"correct": bool(obs["correct"]),
            "attempted": int(obs["attempted"]), "failed": int(obs["failed"])}
    # a driver that runs a reference reads the program's own peak before
    # it; otherwise the peak is what the devices report now
    run.memory_peak_bytes = int(obs.get("memory_peak_bytes") or
                                memory_peak(devices))
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": run.memory_peak_bytes}
    metrics = read_metrics(run, "end_to_end", "end_to_end")
    if args.trace:
        run.reduce_trace(keep=args.keep_trace)
        metrics = read_metrics(run, "per_layer", "layer_metrics")
        summary = run.trace_summary
        if summary:
            device["busy_s"] = summary["busy_s"]
            device["window_s"] = summary["window_s"]
            line["breakdown"] = {"device_ops": summary["device_ops"],
                                 "idle_gaps": summary["idle_gaps"]}
    line["metrics"] = metrics
    line["device"] = device
    # the driver reads the keys above; the rest is for people and sweep.py
    line["checks"] = obs.get("checks", [])
    run.notes["memory_stats"] = devices[0].memory_stats()
    line["notes"] = run.notes
    line["compiles"] = {"setup": run.compiles_setup,
                        "window": run.compiles_window}
    if overrides:
        line["overrides"] = overrides

    kind = "per_layer" if args.trace else "end_to_end"
    declared = {m["name"]: m["unit"]
                for m in spec.metrics(kind, cell["name"])}
    bad = contract.problems(line, declared, bool(args.trace))
    for check in line["checks"]:
        say("check: %s" % check)
    if args.rehearse_cpu:
        print("REHEARSAL (not a result): " + json.dumps(line), flush=True)
        print("*** REHEARSAL %s -- no result line ***"
              % ("FAILED the contract: " + "; ".join(bad) if bad else
                 "passed"), flush=True)
        return 4 if bad else 0
    if bad:
        say("the line breaks the contract, so it is not printed: %s"
            % "; ".join(bad))
        say("it was: " + json.dumps(line))
        return 4
    print(json.dumps(line), flush=True)
    return 0
