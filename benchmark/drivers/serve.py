"""Serving cells: one ``ContinuousBatchingEngine``, an open-loop pacer that
sends each request at its due time whatever the engine does, and client-side
stamps (one consumer per request reading ``handle.tokens()``). Latencies count
from the DUE time, so a stall is charged to every request it delays, and how
late the generator itself ran is reported beside them."""

import gc
import importlib
import threading
import time

import numpy as np

from benchmark import compare, harness, loadgen


def pool_pages(config, geometry, limit_bytes, weight_bytes):
    """Pages for the KV pool, sized at run time as chip_smoke.py does: 90 %
    of the device's memory, less the weights, a reserve for the programs'
    scratch and the state a lane holds whatever its length, over a page's
    device bytes. ``geometry`` is the adapter's ``cache_geometry(config)``:
    the architecture's own arithmetic (which layers hold pages, how wide)."""
    e = config["engine"]
    slots = int(e["max_slots"])
    table_len = -(-int(geometry["max_positions"]) // int(e["page_size"]))
    floor = 1 + slots * table_len             # every lane at full context
    if not limit_bytes:      # a device that states no limit (the CPU of a test)
        return floor
    budget = (int(limit_bytes * 0.9) - weight_bytes - int(e["reserve_bytes"])
              - slots * int(geometry["fixed_device_bytes_per_lane"]))
    return max(floor, int(budget // geometry["page_device_bytes"]))


class Client:
    """One request as its client sees it."""

    __slots__ = ("req", "due", "submitted", "handle", "stamps", "tokens",
                 "error", "thread")

    def __init__(self, req, due):
        self.req, self.due = req, due
        self.submitted = self.handle = self.error = self.thread = None
        self.stamps, self.tokens = [], []

    def consume(self):
        now = time.monotonic
        try:
            for tok in self.handle.tokens():
                self.stamps.append(now())
                self.tokens.append(int(tok))
        except Exception as e:           # the request's terminal failure
            self.error = e

    @property
    def finished(self):
        return (self.error is None and self.handle is not None
                and self.handle.done()
                and len(self.tokens) == self.req["new_tokens"])


def pace(engine, clients, stop):
    """Open loop: sleep to each due time, submit without blocking, start the
    request's consumer. A refused request is a failed one."""
    for c in clients:
        while True:
            wait = c.due - time.monotonic()
            if wait <= 0 or stop.is_set():
                break
            time.sleep(min(wait, 0.05))
        if stop.is_set():
            return
        c.submitted = time.monotonic()
        try:
            c.handle = engine.submit(c.req["prompt"], c.req["new_tokens"],
                                     block=False)
        except Exception as e:
            c.error = e
            continue
        c.thread = threading.Thread(target=c.consume, daemon=True)
        c.thread.start()


def warm_up(engine, config, vocab, seed):
    """Touch every program this traffic uses before the window: a prompt of
    several chunks, the same head again (the prefix-hit path), decode."""
    rng = np.random.RandomState((int(seed) + 7919) % (2 ** 32))
    chunk = int(config["engine"]["prefill_chunk"])
    head = rng.randint(0, vocab, 2 * chunk).astype(np.int32)
    for tail in (chunk // 2, chunk // 2 + 3):
        prompt = np.concatenate(
            [head, rng.randint(0, vocab, tail).astype(np.int32)])
        engine.submit(prompt, 8).result(timeout=1100)
    # two rows prefilled together (prefill_rows) while nothing decodes
    hs = [engine.submit(rng.randint(0, vocab, chunk + 5).astype(np.int32), 4)
          for _ in range(2)]
    for h in hs:
        h.result(timeout=1100)


def live_in(clients, a, b, samples=50):
    """Mean rows in decode and mean cached tokens they hold over [a, b],
    from the client-side stamps."""
    rows = tokens = 0.0
    for t in np.linspace(a, b, samples):
        for c in clients:
            if c.stamps and c.stamps[0] <= t <= c.stamps[-1]:
                rows += 1
                tokens += len(c.req["prompt"]) + np.searchsorted(c.stamps, t)
    return {"rows": rows / samples, "tokens": tokens / samples}


def window_numbers(clients, t0, seconds, cutoff):
    """End-to-end numbers over the requests due in the window. Throughput
    counts THEIR tokens delivered inside it: a stamp lies at or after its
    request's due time, so a faster engine can only bring one into the
    window. The count over every client, lead-in tails included, falls as
    an engine finishes those tails before the window opens (PERF.md section
    7 (f)); it stays in the log beside the new one."""
    measured = [c for c in clients if c.req["due_s"] >= 0]
    t1 = t0 + seconds
    ttft = [((c.stamps[0] if c.stamps else cutoff) - c.due) * 1e3
            for c in measured]
    gaps = [(b - a) * 1e3 for c in measured
            for a, b in zip(c.stamps, c.stamps[1:])]
    def inside(cs):
        return sum(1 for c in cs for s in c.stamps if t0 <= s <= t1)

    delivered, due_delivered = inside(clients), inside(measured)
    failed = [c for c in measured if not c.finished]
    return measured, failed, {
        "ttft_ms": ttft,
        "itl_p95_ms": harness.percentile(gaps, 95),
        "serve_due_tok_per_s": due_delivered / seconds,
    }, {"ttft_p50_p90_p95_p99_ms": [harness.percentile(ttft, q)
                                    for q in (50, 90, 95, 99)],
        "itl_p50_p90_p99_ms": [harness.percentile(gaps, q)
                               for q in (50, 90, 99)],
        "gaps": len(gaps),
        "tokens_in_window": delivered,
        "due_tokens_in_window": due_delivered}


def check_sample(measured, seed, k):
    """A seeded sample of the finished requests with the longest in it."""
    done = [c for c in measured if c.finished]
    if not done:
        return []
    longest = max(done, key=lambda c: len(c.req["prompt"]) + len(c.tokens))
    rest = [c for c in done if c is not longest]
    rng = np.random.RandomState((int(seed) + 104729) % (2 ** 32))
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in pick]


def sample_rows(sample, width):
    """The sampled requests as one (n, width) array of prompt then served
    tokens (zeros behind), with each row's (prompt, total) lengths.
    ``width`` is the geometry's ``max_positions``: one fixed shape a cell."""
    rows = np.zeros((len(sample), int(width)), np.int32)
    spans = []
    for i, c in enumerate(sample):
        row = np.concatenate([c.req["prompt"], np.asarray(c.tokens, np.int32)])
        rows[i, :len(row)] = row
        spans.append((len(c.req["prompt"]), len(row)))
    return rows, spans


def reference_logits(config, seed, rows, weights_map=None):
    """The plain reference, once over ``rows`` (one fixed shape): float32
    logits on the host, from the seed's weights as the adapter makes them
    (the tree its ``build`` loads into the program). ``weights_map`` (the
    precision control, by hand or in a test) rounds them first."""
    adapter = importlib.import_module("benchmark.models." + config["adapter"])
    ref = importlib.import_module(
        "benchmark.reference." + config["reference"])
    w = adapter.weights(config, seed)
    if weights_map is not None:
        w = weights_map(w)
    return np.asarray(ref.forward(w, rows, config))


def start_engine(cell, seed):
    """Set-up: the model with the seed's weights, the engine, its programs
    warm. Returns what a window needs."""
    from bigdl_tpu.serving import ContinuousBatchingEngine

    config = cell["config_json"]
    devs = harness.require_chips(cell["chips"])
    compiles = harness.CompileCount()
    adapter = importlib.import_module("benchmark.models." + config["adapter"])
    vocab = int(config["assumed"]["vocab_real"])
    model = adapter.build(config, seed)
    limit = (devs[0].memory_stats() or {}).get("bytes_limit")
    harness.log(f"[serve] model built: {devs[0].memory_stats()}")
    geometry = adapter.cache_geometry(config)
    max_pages = pool_pages(config, geometry, limit,
                           adapter.weight_bytes(model))
    e = config["engine"]
    kwargs = dict(max_slots=int(e["max_slots"]),
                  prefill_chunk=int(e["prefill_chunk"]),
                  prefill_rows=int(e["prefill_rows"]),
                  page_size=int(e["page_size"]), max_pages=max_pages,
                  queue_capacity=int(e["queue_capacity"]),
                  service_name="bench")
    engine = ContinuousBatchingEngine(model, **kwargs)
    engine.start()
    try:
        warm_up(engine, config, vocab, seed)
    except BaseException:
        engine.stop()
        raise
    harness.log(f"[serve] warm: {compiles.summary()}, max_pages {max_pages}")
    return {"engine": engine, "devs": devs, "adapter": adapter,
            "geometry": geometry, "compiles": compiles,
            "max_pages": max_pages}


def drive_window(ctx, mix, reqs, seconds, trace=False, trace_dir=None):
    """Offer ``reqs`` open loop (lead-in, then the window), wait out the
    drain limit, and return the clients with the window's marks."""
    engine = ctx["engine"]
    stop = threading.Event()
    lead_in = float(mix.get("lead_in_s", 0.0))
    t0 = time.monotonic() + lead_in + 0.05
    clients = [Client(r, t0 + r["due_s"])
               for r in sorted(reqs, key=lambda r: r["due_s"])]
    pacer = threading.Thread(target=pace, args=(engine, clients, stop),
                             daemon=True)
    pacer.start()
    time.sleep(max(0.0, t0 - time.monotonic()))
    opened = time.perf_counter()
    loop_before = engine.stats()["loop"]
    traced, pages_peak, extra = {}, [None], []
    if trace:
        def watch_pages():
            peak = 0
            while not stop.is_set() and time.monotonic() < t0 + seconds:
                peak = max(peak, engine.stats()["paging"]["pool"]
                           ["pages_in_use"])
                time.sleep(0.5)
            pages_peak[0] = peak

        extra = [threading.Thread(target=watch_pages, daemon=True),
                 threading.Thread(
                     target=harness.capture_trace, daemon=True,
                     args=(trace_dir, t0, seconds, mix, traced))]
        for th in extra:
            th.start()
    time.sleep(max(0.0, t0 + seconds - time.monotonic()))
    t_end = time.monotonic()
    loop_after = engine.stats()["loop"]
    queue_at_end = engine.stats()["queue_depth"]
    # drain: what the window started may finish, up to the stated limit
    cutoff = t_end + float(mix["drain_limit_s"])
    pacer.join()
    for c in clients:
        if c.thread is not None:
            c.thread.join(max(0.0, cutoff - time.monotonic()))
    for th in extra:
        th.join()
    stop.set()
    return {"clients": clients, "t0": t0, "t_end": t_end, "opened": opened,
            "cutoff": min(cutoff, time.monotonic()),
            "loop_before": loop_before, "loop_after": loop_after,
            "queue_at_end": queue_at_end, "traced": traced,
            "pages_peak": pages_peak[0],
            "compiles_in_window": ctx["compiles"].between(t0, t_end)}


def run(cell, seed, seconds, trace, t_start, trace_dir=None):
    config, mix = cell["config_json"], cell["traffic_json"]
    reqs = loadgen.open_loop_requests(
        mix, seed, seconds, int(config["assumed"]["vocab_real"]))
    harness.say(loadgen.describe_requests(reqs, seconds))
    ctx = start_engine(cell, seed)
    engine, adapter = ctx["engine"], ctx["adapter"]
    width = ctx["geometry"]["max_positions"]
    try:
        w = drive_window(ctx, mix, reqs, seconds, trace, trace_dir)
        setup_s = w["opened"] - t_start
        clients, traced = w["clients"], w["traced"]
        measured, failed, e2e, extra_numbers = window_numbers(
            clients, w["t0"], seconds, w["cutoff"])
        finished = [c for c in measured if c.finished]
        streamed_equal = all(
            np.array_equal(np.asarray(c.handle.result())[len(c.req["prompt"]):],
                           np.asarray(c.tokens, np.int32)) for c in finished)
        record = {
            "programs": config["programs"], "sizes": config["sizes"],
            "scopes": config.get("scopes", {}),
            "ttft_ms": e2e.pop("ttft_ms"),
            "late_ms": [(c.submitted - c.due) * 1e3 for c in measured
                        if c.submitted is not None],
            "queue_wait_ms": [
                (c.handle.admitted_at - c.handle.submitted_at) * 1e3
                for c in measured if c.handle is not None
                and c.handle.admitted_at is not None],
            "prompt_tokens": sum(len(c.req["prompt"]) for c in measured
                                 if c.handle is not None),
            "prefix_tokens": sum(int(c.handle.prefix_tokens or 0)
                                 for c in measured if c.handle is not None),
            "max_pages": ctx["max_pages"], "pages_peak": w["pages_peak"],
            "loop_before": w["loop_before"], "loop_after": w["loop_after"],
            "jit_compiles": engine.stats()["jit_compiles"],
        }
        if traced:
            record["live_in_trace"] = live_in(clients, traced["a"],
                                              traced["b"])
        device = harness.device_block(ctx["devs"])
    finally:
        engine.stop()
    sample = check_sample(measured, seed,
                          int(config["check"]["sample_requests"]))
    rows, spans = sample_rows(sample, width)
    # the engine's pool goes; the model it served stays for one more pass:
    # its own paged-prefill logits over the sampled rows
    served_model, kv_dtype = engine.model, engine.kv_dtype
    ctx.clear()
    del engine
    gc.collect()
    paged = adapter.paged_logits(served_model, kv_dtype, config, rows)
    # the reference does not fit beside the program's state: that goes too
    del served_model
    gc.collect()
    logits = reference_logits(config, seed, rows)
    check_rows = compare.serving_rows(
        logits, paged, rows, spans, streamed_equal, w["compiles_in_window"],
        config["check"]["limits"])
    harness.say({"reference": compare.reference_facts(logits, rows, spans)})
    for r in check_rows:
        harness.say({"check": r})
    if traced:
        extra_numbers["itl_p50_ms_while_traced"] = harness.median(
            [(b - a) * 1e3 for c in measured
             for a, b in zip(c.stamps, c.stamps[1:])
             if traced["a"] <= a and b <= traced["b"]])
    harness.say({"window": {**extra_numbers, "setup_s": setup_s,
                            "requests": len(measured),
                            "compared_requests": len(sample),
                            "compared_tokens": int(sum(
                                b - a for a, b in spans)),
                            "queue_at_end": w["queue_at_end"],
                            "jit_compiles": record["jit_compiles"]}})
    out = {"correct": bool(sample) and all(r["ok"] for r in check_rows),
           "attempted": len(measured), "failed": len(failed),
           "device": device, "checks": check_rows,
           "values": {**e2e, "setup_s": setup_s}, "record": record,
           # for the tools' control readings and the tests
           "compared": {"rows": rows, "spans": spans, "paged_logits": paged,
                        "reference_logits": logits}}
    if trace:
        out["trace"] = (traced["b"] - traced["a"]) if traced else None
    return out
