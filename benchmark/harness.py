"""What every run shares: the manifest, the cell's files found by name, the
device that must be a chip, the compile cache, compile counting, order
statistics and the result line."""

import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class BenchmarkError(Exception):
    """The run cannot produce a result line."""


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def manifest():
    return load_json(ROOT, "BENCHMARK.json")


def load_cell(name, man=None):
    """The manifest entry of cell ``name`` with its configuration and traffic
    files, each found by the name the manifest gives. A configuration whose
    ``scopes`` re-group what the scope reader knows is refused here."""
    from benchmark import program_scopes       # it imports this module

    man = man or manifest()
    cells = {w["name"]: w for w in man["workloads"]}
    if name not in cells:
        raise BenchmarkError(f"no workload {name!r}; have {sorted(cells)}")
    cell = dict(cells[name])
    files = {c["name"]: c["file"] for c in man["configs"]}
    cell["config_json"] = load_json(ROOT, files[cell["config"]])
    program_scopes.vocabulary(cell["config_json"].get("scopes"))
    cell["traffic_json"] = load_json(HERE, "traffic", cell["traffic"] + ".json")
    cell["end_to_end"] = [m for m in man["end_to_end"]
                          if name in m.get("workloads", [name])]
    cell["per_layer"] = [m for m in man["per_layer"]
                         if name in m.get("workloads", [name])]
    return cell


def load_module(kind, name):
    """``benchmark/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def say(obj):
    print(json.dumps(obj), flush=True)


# ------------------------------------------------------------------ device
def require_chips(n):
    """The accelerator devices this cell runs on; anything else is an error.
    There is no CPU fallback: a timing from a CPU is not a result."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchmarkError(
            f"jax reports platform {devs[0].platform!r}: the benchmark runs "
            "on a TPU only")
    if len(devs) < n:
        raise BenchmarkError(f"cell needs {n} chips, jax sees {len(devs)}")
    return devs[:n]


def peaks_for(kind):
    table = load_json(HERE, "peaks.json")["device_kinds"]
    if kind not in table:
        raise BenchmarkError(f"device kind {kind!r} is not in peaks.json")
    return table[kind]


def device_block(devs):
    """The result line's ``device``. ``memory_peak_bytes`` is the fullest
    chip's ``peak_bytes_in_use + peak_bytes_reserved``: the allocator's peak
    counts arrays and leaves out the scratch the runtime reserves for the
    running executable (a ResNet-50 step: 1.1 GB of arrays, 14.6 GB of
    scratch for its activations; PERF.md section 7 says what this reading
    rests on). Both parts are printed, so either can be read alone."""
    def parts(d):
        s = d.memory_stats() or {}
        return (int(s.get("peak_bytes_in_use", 0)),
                int(s.get("peak_bytes_reserved", 0)))

    used, reserved = max((parts(d) for d in devs), key=sum)
    log(f"[device] memory_stats of device 0: {devs[0].memory_stats()}")
    say({"memory": {"peak_bytes_in_use": used,
                    "peak_bytes_reserved": reserved,
                    "bytes_limit": (devs[0].memory_stats() or {}).get(
                        "bytes_limit")}})
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": used + reserved}


def enable_cache():
    """The repo's one compile-cache function says where the cache lives
    (JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache); this process
    also keeps the sub-second programs, which otherwise recompile in every
    run (PERF.md, PR 23)."""
    import jax

    from bigdl_tpu.utils.compile_cache import enable_persistent_cache

    where = enable_persistent_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return where


class CompileCount:
    """Backend compilations of this process, with when each ended."""

    def __init__(self):
        import time

        from jax import monitoring

        self._now = time.monotonic
        self.at, self.seconds, self.cache_hits = [], [], 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.at.append(self._now())
            self.seconds.append(float(secs))

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def between(self, t0, t1):
        return sum(1 for t in self.at if t0 < t <= t1)

    def summary(self):
        return {"compiles": len(self.at),
                "compile_seconds": round(sum(self.seconds), 3),
                "cache_hits": self.cache_hits}


class HostWatch:
    """What the host did to the window, for the log (no metric reads it): a
    thread that sleeps ``tick`` seconds at a time and notes how late it woke
    (something held the interpreter or the whole process, not only the timed
    loop), this process's CPU seconds across the window, and the host's CPU
    model (machines of one kind differ by it). /proc/stat and getrusage's
    switches and faults read 0 on the chip's machine, so they are not read."""

    def __init__(self, tick=0.1):
        import threading

        self.tick, self.late, self._stop = tick, [], threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="bench-hostwatch")
        self._cpu = [None, None]

    def _run(self):
        import time

        while not self._stop.is_set():
            a = time.monotonic()
            time.sleep(self.tick)
            late = time.monotonic() - a - self.tick
            if late > 0.02:
                self.late.append((a, late))

    def start(self):
        import time

        self._cpu[0] = time.process_time()
        self._thread.start()

    def stop(self):
        import time

        self._cpu[1] = time.process_time()
        self._stop.set()
        self._thread.join()

    @staticmethod
    def cpu_model():
        try:
            with open("/proc/cpuinfo") as f:
                for ln in f:
                    if ln.startswith("model name"):
                        return ln.split(":", 1)[1].strip()
        except OSError:
            pass
        return None

    def summary(self, t0, t1):
        late = sorted(((round(a - t0, 2), round(d * 1e3, 1))
                       for a, d in self.late if t0 <= a <= t1),
                      key=lambda p: -p[1])
        return {"cpu_model": self.cpu_model(), "cpus": os.cpu_count(),
                "process_cpu_s": round(self._cpu[1] - self._cpu[0], 3),
                "ticker_late_over_20ms": len(late),
                "ticker_latest_at_s_ms": late[:5]}


TRACE_MARKER = "bench/window"


def capture_trace(trace_dir, t0, seconds, mix, out):
    """Trace ``mix["trace_seconds"]`` seconds in the middle of the window
    that opened at ``t0`` (monotonic). The Python tracer is off (it slows the
    host); ``mix["trace_host_level"]`` says whether host spans are recorded
    (1: the program's TraceAnnotations label the idle gaps; 0 where recording
    them slows the traced path itself, see PERF.md). Leaves the traced
    interval's ends in ``out``."""
    import time

    import jax

    span = min(float(mix.get("trace_seconds", 3.0)), seconds / 2)
    time.sleep(max(0.0, t0 + (seconds - span) / 2 - time.monotonic()))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = int(mix.get("trace_host_level", 1))
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    # starting and stopping the profiler stalls the host for a second or
    # more; the marker span says, on the trace's own clock, which part of
    # the trace is the steady window the metrics are read from
    time.sleep(0.5)
    a = time.monotonic()
    with jax.profiler.TraceAnnotation(TRACE_MARKER):
        time.sleep(span)
    b = time.monotonic()
    jax.profiler.stop_trace()
    out.update(a=a, b=b)


# -------------------------------------------------------------- statistics
def percentile(values, q):
    """The q-th percentile (0..100) by linear interpolation between order
    statistics; None for no values."""
    xs = sorted(values)
    if not xs:
        return None
    if len(xs) == 1:
        return float(xs[0])
    k = (len(xs) - 1) * q / 100.0
    lo = int(k)
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (k - lo))


def median(values):
    return percentile(values, 50)


def metric_entries(wanted, values):
    """The ``metrics`` object of the result line: each wanted metric that
    has a value, as measured, with its unit."""
    out = {}
    for m in wanted:
        v = values.get(m["name"])
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out
