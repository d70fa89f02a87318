#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main paths once on ONE TPU chip, through the entry points a user
calls, at the full width of models the repo supports (weights and data are
random, made from ``--seed``), and checks what comes out by the repo's own
means:

  device   jax sees a TPU; its peaks resolve from the table; what XLA's cost
           analysis returns here (lowered vs compiled); native library state
  kernel   ops.flash_attention forward + backward, bf16, causal, T=4096,
           head_dim 64 and 128, GQA 4:1 — compiled (tpu_custom_call in the
           executable), allclose to nn.attention.dot_product_attention
  trainer  ResNet-50 (224x224x3 NHWC, 1000 classes): (a) models.perf.run_perf,
           the bf16 step bench.py runs; (b) LocalOptimizer(...).optimize()
  server   ContinuousBatchingEngine, paged, over a TransformerLM at GPT-2
           Large's widths: streamed requests, greedy parity with a lone
           model.generate row, a prefix hit, flat jit_compiles, healthz

``--chips 4`` runs ONLY the paths that exist across chips, each against its
one-device counterpart: DistriOptimizer(parameter_sync="sharded") on a
("data", 4) mesh vs LocalOptimizer, and the engine on a ("model", 4) mesh vs
the same engine unsharded.

Every phase prints one JSON line (smoke observations, NOT benchmark results).
Any phase that raises fails the script. The last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}`` only
when every phase passed on a TPU; without a TPU the script exits non-zero and
its last line says ``"ok": false``. ``--rehearse`` runs the same code at tiny
sizes on whatever device jax has (the CPU rehearsal) and can never print
``"ok": true``.

One process: it touches jax itself and starts no child that needs the chip.
"""

import argparse
import gc
import json
import os
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))

# sizes a user would call real; "tiny" changes sizes only (CPU rehearsal)
SIZES = {
    "real": dict(
        flash=dict(batch=1, heads=8, kv_heads=2, seq=4096, head_dims=(64, 128)),
        resnet=dict(depth=50, image=224, classes=1000, batch=256,
                    warmup=2, steps=5),
        # GPT-2 Large (Radford et al. 2019): 36 layers, d_model 1280,
        # 20 heads x 64, context 1024; vocabulary 50257 padded to 50304
        lm=dict(vocab=50304, embed=1280, heads=20, layers=36, max_len=1024),
        serve=dict(slots=8, page_size=16, chunk=128, rows=2,
                   prompt=(64, 512), new=(32, 128), prefix=256, shared_tail=64,
                   requests=8, parity=2, reserve_bytes=2 << 30,
                   tp_pages=640, tp_requests=4),
    ),
    "tiny": dict(
        flash=dict(batch=1, heads=4, kv_heads=1, seq=256, head_dims=(64, 128)),
        resnet=dict(depth=18, image=224, classes=10, batch=8,
                    warmup=2, steps=5),
        lm=dict(vocab=256, embed=64, heads=4, layers=2, max_len=128),
        serve=dict(slots=4, page_size=4, chunk=8, rows=2,
                   prompt=(8, 40), new=(4, 12), prefix=16, shared_tail=8,
                   requests=8, parity=2, reserve_bytes=0,
                   tp_pages=160, tp_requests=4),
    ),
}

NOTE = "smoke observation, not a benchmark result"


def say(obj):
    print(json.dumps(obj), flush=True)


def log(*a, **k):
    print(*a, file=sys.stderr, flush=True, **k)


class CompileLog:
    """Process-wide compile telemetry from jax.monitoring: every backend
    compile (count, seconds, when) and every persistent-cache hit."""

    def __init__(self):
        from jax import monitoring

        self.compiles = []      # (monotonic time, seconds)
        self.cache_hits = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compiles.append((time.monotonic(), float(secs)))

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def mark(self):
        return len(self.compiles), self.cache_hits

    def since(self, mark):
        n0, h0 = mark
        new = self.compiles[n0:]
        return {"compiles": len(new),
                "compile_seconds": round(sum(s for _, s in new), 3),
                "compile_cache_hits": self.cache_hits - h0}

    def count_after(self, t_monotonic):
        return sum(1 for t, _ in self.compiles if t > t_monotonic)


class SmokeFailure(Exception):
    """A check of the smoke did not hold."""


def check(ok, why):
    """Raise unless ``ok`` (not ``assert``: that vanishes under -O)."""
    if not ok:
        raise SmokeFailure(why)


def phase(ctx, name, fn):
    """Run one phase; a raise propagates and fails the script."""
    log(f"[smoke] phase {name} ...")
    mark = ctx["compiles"].mark()
    t0 = time.perf_counter()
    observed = fn(ctx)
    line = {"phase": name, "passed": True,
            "seconds": round(time.perf_counter() - t0, 3),
            **ctx["compiles"].since(mark),
            "compile_cache_dir": ctx["cache_dir"],
            "note": NOTE, **observed}
    say(line)
    gc.collect()


def memory(dev):
    s = dev.memory_stats() or {}
    return {k: s.get(k) for k in
            ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")}


def on_platform(tree, platform):
    import jax

    return all(d.platform == platform
               for leaf in jax.tree.leaves(tree) for d in leaf.devices())


# ------------------------------------------------------------------ device
def phase_device(ctx):
    import jax
    import jax.numpy as jnp

    import bigdl_tpu.native as native
    from bigdl_tpu.observability.costmodel import (
        device_peaks, executable_cost, program_cost,
    )

    dev = ctx["dev"]
    peaks = device_peaks(dev)   # an unknown kind raises
    if not ctx["rehearse"]:
        check(dev.platform == "tpu", dev.platform)
    check(peaks["source"] == "table", peaks)
    # what XLA prices on this backend: the lowering vs the executable
    f = jax.jit(lambda a, b: a @ b)
    x = jnp.ones((512, 512), jnp.bfloat16)
    lowered_cost = program_cost(f, x, x)
    compiled_cost = executable_cost(f.lower(x, x).compile())
    return {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()), "jax": jax.__version__,
        "peaks": peaks,
        "cost_analysis": {
            "lowered": None if lowered_cost is None else lowered_cost["flops"],
            "compiled": (None if compiled_cost is None
                         else compiled_cost["flops"])},
        "native_library": ("built" if native.native_available()
                           else "absent (python implementations in use)"),
        "memory": memory(dev),
    }


# ------------------------------------------------------------------ kernel
def phase_kernel(ctx):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bigdl_tpu.nn.attention import dot_product_attention
    from bigdl_tpu.ops.flash_attention import flash_attention

    z = ctx["sizes"]["flash"]
    b, h, hk, t = z["batch"], z["heads"], z["kv_heads"], z["seq"]
    group = h // hk
    rows = []
    for d in z["head_dims"]:
        ks = jax.random.split(jax.random.PRNGKey(ctx["seed"] + d), 4)
        q = jax.random.normal(ks[0], (b, h, t, d), jnp.bfloat16)
        k = jax.random.normal(ks[1], (b, hk, t, d), jnp.bfloat16)
        v = jax.random.normal(ks[2], (b, hk, t, d), jnp.bfloat16)
        w = jax.random.normal(ks[3], (b, h, t, d), jnp.bfloat16)

        def flash(q, k, v):
            return flash_attention(q, k, v, causal=True)

        def dense(q, k, v):
            return dot_product_attention(
                q, jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1),
                causal=True)

        def with_grads(fn):
            def run(q, k, v, w):
                def loss(q, k, v):
                    return jnp.sum(fn(q, k, v).astype(jnp.float32)
                                   * w.astype(jnp.float32))
                return (fn(q, k, v),) + jax.grad(loss, (0, 1, 2))(q, k, v)
            return jax.jit(run)

        exe = with_grads(flash).lower(q, k, v, w).compile()
        text = exe.as_text()
        custom_calls = text.count("tpu_custom_call")
        if not ctx["rehearse"]:
            # compiled Mosaic kernel, forward and backward: neither the
            # interpreter nor the dense give-way for a length that tiles
            check(custom_calls >= 2,
                  f"flash d={d}: {custom_calls} tpu_custom_call in the "
                  "executable — interpreted or dense path taken")
        got = jax.block_until_ready(exe(q, k, v, w))
        want = jax.block_until_ready(with_grads(dense)(q, k, v, w))
        errs = {}
        for name, g, r in zip(("out", "dq", "dk", "dv"), got, want):
            g = np.asarray(g, np.float32)
            r = np.asarray(r, np.float32)
            check(np.isfinite(g).all(), f"flash d={d}: non-finite {name}")
            check(g.shape == r.shape, 'g.shape == r.shape')
            # bf16 tolerance, relative to the reference's scale
            err = float(np.max(np.abs(g - r)) / max(np.max(np.abs(r)), 1e-6))
            errs[name] = round(err, 5)
            check(err < 3e-2, f"flash d={d}: {name} off by {err:.4f}")
        rows.append({"head_dim": d, "tpu_custom_calls": custom_calls,
                     "max_rel_err_vs_dense": errs})
    return {"shape": {"batch": b, "heads": h, "kv_heads": hk, "seq": t,
                      "dtype": "bfloat16", "causal": True},
            "compiled_not_interpreted": not ctx["rehearse"],
            "cases": rows}


# ----------------------------------------------------------------- trainer
class LossLog:
    """The train-summary hook both optimizers feed once per iteration:
    keeps the loss series and the process compile count at each step."""

    def __init__(self, compiles):
        self.losses, self.compiles_at = [], []
        self._compiles = compiles

    def add_scalar(self, tag, value, step):
        if tag == "Loss":
            self.losses.append(float(value))
            self.compiles_at.append(len(self._compiles.compiles))


def resnet_samples(z, n, seed):
    import numpy as np

    from bigdl_tpu.dataset.sample import Sample

    rng = np.random.RandomState(seed)
    x = rng.standard_normal((n, z["image"], z["image"], 3)).astype(np.float32)
    y = rng.randint(1, z["classes"] + 1, size=n).astype(np.float32)
    return [Sample(x[i], y[i:i + 1]) for i in range(n)]


def build_resnet(z, seed):
    from bigdl_tpu.models.perf import build_model
    from bigdl_tpu.utils import random as rnd

    rnd.set_seed(seed)
    model, _, _ = build_model(f"resnet{z['depth']}", z["classes"],
                              format="NHWC")
    return model


def check_training(name, model, before, log_, steps, platform):
    """The checks every optimizer run must pass: a finite loss that moves,
    parameters that moved, results living on the accelerator, and no
    compile after the warm-up iterations."""
    import numpy as np

    losses = log_.losses
    check(len(losses) == steps, (name, losses))
    check(np.isfinite(losses).all(), (name, losses))
    check(len(set(losses)) > 1, f"{name}: loss did not change: {losses}")
    after, _ = model.get_parameters()
    check(on_platform(model.params_dict(), platform),
          f"{name}: parameters are not on {platform}")
    delta = float(np.max(np.abs(np.asarray(after) - before)))
    check(np.isfinite(delta) and delta > 0.0, f"{name}: parameters unchanged")
    late = log_.compiles_at[-1] - log_.compiles_at[1]
    check(late == 0, f"{name}: {late} compilations after warm-up")
    return {"losses": [round(v, 5) for v in losses],
            "max_param_delta": delta, "compiles_after_warmup": late}


def phase_trainer_perf(ctx):
    import jax.numpy as jnp
    import numpy as np

    from bigdl_tpu.models.perf import run_perf

    z = ctx["sizes"]["resnet"]
    s = run_perf(f"resnet{z['depth']}", batch_size=z["batch"],
                 iterations=z["steps"], warmup=z["warmup"],
                 dtype=jnp.bfloat16, format="NHWC", master_f32=True,
                 class_num=z["classes"], log=log)
    ended = time.monotonic()
    check(np.isfinite(s["loss"]) and np.isfinite(s["warmup_loss"]), s)
    check(s["loss"] != s["warmup_loss"], f"loss did not change: {s}")
    check(s["device_platform"] == ctx["dev"].platform, s)
    # FLOPs come from the executable that runs — on the CPU and on the chip
    check(s.get("cost_source") == "xla", s)
    late = ctx["compiles"].count_after(ended - s["time_s"])
    check(late == 0, f"{late} compilations inside the timed steps")
    return {"entry": "models.perf.run_perf (the bench.py / bigdl-tpu-perf step)",
            "model": s["model"], "image": z["image"], "classes": z["classes"],
            "batch": z["batch"], "dtype": "bf16 compute, f32 masters",
            "format": "NHWC", "warmup_steps": z["warmup"], "steps": z["steps"],
            "warmup_loss": s["warmup_loss"], "loss": s["loss"],
            "compile_and_warmup_s": s["warmup_s"],
            "ms_per_step_observed": s["ms_per_iter"],
            "flops_source": s.get("cost_source"),
            "flops_per_step": s.get("flops_per_iter"),
            "compiles_in_timed_steps": late,
            "memory": memory(ctx["dev"])}


def run_local_optimizer(ctx, z, batch, grad_accum=1):
    import numpy as np

    from bigdl_tpu import nn
    from bigdl_tpu.dataset.dataset import DataSet
    from bigdl_tpu.optim import SGD, LocalOptimizer, Trigger

    model = build_resnet(z, ctx["seed"])
    before = np.asarray(model.get_parameters()[0]).copy()
    log_ = LossLog(ctx["compiles"])
    opt = LocalOptimizer(
        model=model, dataset=DataSet.array(resnet_samples(z, batch,
                                                          ctx["seed"])),
        criterion=nn.CrossEntropyCriterion(), batch_size=batch,
        end_when=Trigger.max_iteration(z["steps"]))
    opt.set_optim_method(SGD(learning_rate=0.01, momentum=0.9))
    opt.set_train_summary(log_)
    if grad_accum > 1:
        opt.set_gradient_accumulation(grad_accum)
    opt.optimize()
    return model, before, log_


def phase_trainer_local(ctx):
    z = ctx["sizes"]["resnet"]
    batch = z["batch"]
    model, before, log_ = run_local_optimizer(ctx, z, batch)
    out = check_training("LocalOptimizer", model, before, log_, z["steps"],
                         ctx["dev"].platform)
    return {"entry": "optim.LocalOptimizer(...).optimize()",
            "model": f"resnet{z['depth']}", "image": z["image"],
            "classes": z["classes"], "batch": batch,
            "dtype": "f32 (this loop passes no compute_dtype)",
            "format": "NHWC", "steps": z["steps"], **out,
            "memory": memory(ctx["dev"])}


# ------------------------------------------------------------------ server
def build_lm(ctx):
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.models.transformer import TransformerLM
    from bigdl_tpu.utils import random as rnd

    z = ctx["sizes"]["lm"]
    rnd.set_seed(ctx["seed"])
    model = TransformerLM(z["vocab"], embed_dim=z["embed"],
                          num_heads=z["heads"], num_layers=z["layers"],
                          max_len=z["max_len"])
    model.evaluate()
    # bf16 weights; the engine derives its KV dtype from them
    model.load_params_dict(jax.tree.map(
        lambda a: a.astype(jnp.bfloat16)
        if jnp.issubdtype(a.dtype, jnp.floating) else a,
        model.params_dict()))
    return model


def make_requests(ctx, n):
    """n prompts from the seed; requests 0 and 1 share a prefix."""
    import numpy as np

    z, vocab = ctx["sizes"]["serve"], ctx["sizes"]["lm"]["vocab"]
    rng = np.random.RandomState(ctx["seed"])
    prefix = rng.randint(0, vocab, size=z["prefix"])
    reqs = []
    for i in range(n):
        if i < 2:
            prompt = np.concatenate(
                [prefix, rng.randint(0, vocab, size=z["shared_tail"])])
        else:
            prompt = rng.randint(0, vocab,
                                 size=rng.randint(z["prompt"][0],
                                                  z["prompt"][1] + 1))
        reqs.append((prompt.astype(np.int32),
                     int(rng.randint(z["new"][0], z["new"][1] + 1))))
    return reqs


# memory_analysis of the compile rehearsal (v5e): the pool's
# (pages, 20, 16, 64) bf16 leaves take 9/8 of their logical bytes on the
# device; decode-step and prefill-chunk scratch stay under 0.45 GiB at
# 3600 pages, and the pool is aliased in full (donation takes).
POOL_DEVICE_FACTOR = 1.125


def page_bytes(ctx):
    z, s = ctx["sizes"]["lm"], ctx["sizes"]["serve"]
    return z["layers"] * 2 * s["page_size"] * z["embed"] * 2   # k+v, bf16


def serve(ctx, model, reqs, label, **engine_kw):
    """Stream ``reqs`` through one engine: request 0 alone first (it warms
    every program and donates its prefix), then the rest together."""
    import numpy as np

    from bigdl_tpu.serving import ContinuousBatchingEngine

    z = ctx["sizes"]["serve"]
    with ContinuousBatchingEngine(
            model, max_slots=z["slots"], prefill_chunk=z["chunk"],
            prefill_rows=z["rows"], page_size=z["page_size"],
            service_name=label, **engine_kw) as eng:
        streamed, handles = {}, []
        if eng.mesh is not None:
            eng._step_jit = CompiledOnce(eng._step_jit)
            eng._chunk_jit = CompiledOnce(eng._chunk_jit)

        def drain(i, h):
            streamed[i] = [int(t) for t in h.tokens()]

        h0 = eng.submit(*reqs[0])
        drain(0, h0)
        handles.append(h0)
        compiles_first = eng.stats()["jit_compiles"]
        threads = []
        for i, (prompt, n) in enumerate(reqs[1:], start=1):
            h = eng.submit(prompt, n)
            handles.append(h)
            th = threading.Thread(target=drain, args=(i, h))
            th.start()
            threads.append(th)
        for th in threads:
            th.join()
        rows = [np.asarray(h.result()) for h in handles]
        stats = eng.stats()
        health = eng.healthz()
        for i, ((prompt, n), row) in enumerate(zip(reqs, rows)):
            check(row.shape == (len(prompt) + n,), (i, row.shape))
            check(streamed[i] == [int(t) for t in row[len(prompt):]],
                  f"{label}: streamed tokens of request {i} differ "
                  "from its row")
        check(health["status"] == "ok", health)
        check(stats["jit_compiles"] == compiles_first,
              f"{label}: jit_compiles moved {compiles_first} -> "
              f"{stats['jit_compiles']} after the first request")
        check(handles[1].prefix_tokens > 0,
              f"{label}: the prefix-sharing request reused nothing")
        cost = stats["cost"]["kinds"]
        placed = {}
        if eng.mesh is not None:
            placed = {
                "params": shard_report(eng._params),
                "kv_pool": shard_report(eng._kv_pool),
                "collectives": {
                    "decode_step": collectives(eng._step_jit.text),
                    "prefill_chunk": collectives(eng._chunk_jit.text)}}
        return rows, {
            **placed,
            "jit_compiles": stats["jit_compiles"],
            "prefix_tokens_reused": int(handles[1].prefix_tokens),
            "healthz": health["status"],
            "flops_source": {k: v["flops_source"] for k, v in cost.items()},
            "pools": stats["mesh"]["pools"],
            "paging": {"page_size": stats["paging"]["page_size"],
                       "table_len": stats["paging"]["table_len"],
                       **{k: stats["paging"]["pool"][k] for k in
                          ("max_pages", "allocated_total", "shared_total",
                           "cow_forks_total")}},
        }


def reference_rows(model, reqs, which, bucket):
    """Lone ``model.generate`` rows (greedy): the parity oracle. One
    decode program serves every length (``bucket_tokens``)."""
    import jax.numpy as jnp
    import numpy as np

    out = {}
    for i in which:
        prompt, n = reqs[i]
        out[i] = np.asarray(model.generate(
            jnp.asarray(prompt)[None], n, bucket_tokens=bucket))[0]
    return out


# bf16 tolerance, relative to the logits' scale (the kernel phase holds
# flash attention to the same bound against its dense reference)
TIE_TOLERANCE = 3e-2


def greedy_equivalent(model, prompt, got, want, what):
    """Greedy parity of two rows that should be token-identical. Exact
    equality passes. Where they part, the two programs round bf16 sums in
    a different order, and that may only decide a near-tie: at the first
    differing position both tokens must be within ``TIE_TOLERANCE`` of the
    top logit of the model's own forward over the shared prefix. After
    that position the histories differ and nothing more is compared. A
    wrong page, head or position fails this: it picks a token far from
    the top. Returns what was found."""
    import jax.numpy as jnp
    import numpy as np

    got, want = np.asarray(got), np.asarray(want)
    check(got.shape == want.shape, (what, got.shape, want.shape))
    if np.array_equal(got, want):
        return {"exact": True, "tokens": int(len(got) - len(prompt))}
    i = int(np.argmax(got != want))
    check(i >= len(prompt), f"{what}: the prompt itself differs at {i}")
    # one forward shape for every case: causal, so padding past i is inert
    ids = np.zeros((1, model.max_len), np.int32)
    ids[0, :i] = want[:i]
    logits = np.asarray(model(jnp.asarray(ids))[0, i - 1], np.float32)
    scale = float(np.max(np.abs(logits)))
    gaps = {name: float(logits.max() - logits[int(tok)]) / scale
            for name, tok in (("got", got[i]), ("want", want[i]))}
    check(max(gaps.values()) <= TIE_TOLERANCE,
          f"{what}: rows part at index {i} (generated token "
          f"{i - len(prompt)}) and it is no near-tie: gap to the top "
          f"logit, relative to the logits' scale, {gaps}")
    return {"exact": False, "tokens": int(len(got) - len(prompt)),
            "parted_at_generated_token": i - len(prompt),
            "gap_to_top_logit_rel": {k: round(v, 5) for k, v in gaps.items()}}


def phase_server(ctx):
    import numpy as np

    z, s = ctx["sizes"]["lm"], ctx["sizes"]["serve"]
    dev = ctx["dev"]
    model = build_lm(ctx)
    weights = sum(int(a.nbytes) for a in
                  __import__("jax").tree.leaves(model.params_dict()))
    table_len = -(-z["max_len"] // s["page_size"])
    floor = 1 + 2 * s["slots"] * table_len
    limit = (dev.memory_stats() or {}).get("bytes_limit")
    if limit and not ctx["rehearse"]:
        # most of what the weights leave: the reserve covers the programs'
        # scratch and the lone-generate reference's own dense cache
        budget = int(limit * 0.9) - weights - s["reserve_bytes"]
        max_pages = max(floor, int(budget // (page_bytes(ctx)
                                              * POOL_DEVICE_FACTOR)))
    else:
        max_pages = floor
    reqs = make_requests(ctx, s["requests"])
    rows, seen = serve(ctx, model, reqs, "smoke", max_pages=int(max_pages))
    gc.collect()   # the stopped engine's pool goes before the reference runs
    refs = reference_rows(model, reqs, range(s["parity"]), s["new"][1])
    parity = {i: greedy_equivalent(model, reqs[i][0], rows[i], ref,
                                   f"request {i}: engine vs model.generate")
              for i, ref in refs.items()}
    mem = memory(dev)
    return {"entry": "serving.ContinuousBatchingEngine (paged)",
            "model": {"family": "GPT-2 Large widths", **z,
                      "dtype": "bf16 weights and KV", "depth_cut": False},
            "weights_bytes": weights,
            "max_pages": int(max_pages),
            "pool_bytes": int(max_pages) * page_bytes(ctx),
            "requests": [{"prompt": len(p), "new": n} for p, n in reqs],
            "greedy_parity_with_lone_generate": parity,
            **seen, "memory": mem,
            "peak_over_pool_plus_weights": (
                None if not mem["peak_bytes_in_use"] else round(
                    mem["peak_bytes_in_use"]
                    / (int(max_pages) * page_bytes(ctx) + weights), 3))}


# ------------------------------------------------------- across four chips
def per_device():
    import jax

    return [{"device": d.id, **memory(d)} for d in jax.devices()]


class CompiledOnce:
    """Stand-in for one jitted program of a mesh path: compiles it ahead
    of time on its first call — the one compile the program costs anyway —
    keeps the executable's text for the collectives report, and runs that
    executable from then on (a changed signature raises, it never
    recompiles)."""

    def __init__(self, jitted):
        self._jitted, self._exe, self.text = jitted, None, None

    def __call__(self, *args):
        if self._exe is None:
            self._exe = self._jitted.lower(*args).compile()
            self.text = self._exe.as_text()
        return self._exe(*args)

    def _cache_size(self):
        return int(self._exe is not None)


def shard_report(tree):
    """Where a live tree sits: bytes per device and its shard shapes."""
    import jax

    held = {d.id: 0 for d in jax.devices()}
    shapes = set()
    for leaf in jax.tree.leaves(tree):
        for sh in getattr(leaf, "addressable_shards", ()):
            held[sh.device.id] += int(sh.data.nbytes)
            if leaf.ndim:
                shapes.add(f"{tuple(leaf.shape)} -> {tuple(sh.data.shape)}")
    return {"bytes_per_device": [held[d.id] for d in jax.devices()],
            "shard_shapes": sorted(shapes)[:6]}


def check_spread(name, holdings):
    """Every device holds its share; device 0 does not hold everything."""
    check(all(b > 0 for b in holdings),
          f"{name}: a device holds nothing: {holdings}")
    check(holdings[0] < sum(holdings), f"{name}: device 0 holds everything")


def collectives(text):
    names = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
             "collective-permute")
    return {n: text.count(f" {n}(") + text.count(f" {n}-start(")
            for n in names if f" {n}" in text}


def phase_distri(ctx):
    import jax
    import numpy as np

    from bigdl_tpu import nn
    from bigdl_tpu.dataset.dataset import DataSet
    from bigdl_tpu.optim import SGD, Trigger
    from bigdl_tpu.parallel import DistriOptimizer, Engine

    z = ctx["sizes"]["resnet"]
    n_dev = len(jax.devices())
    batch = z["batch"]
    mesh = Engine.create_mesh([("data", n_dev)])
    model = build_resnet(z, ctx["seed"])
    before = np.asarray(model.get_parameters()[0]).copy()
    log_ = LossLog(ctx["compiles"])
    opt = DistriOptimizer(
        model=model, dataset=DataSet.array(resnet_samples(z, batch,
                                                          ctx["seed"])),
        criterion=nn.CrossEntropyCriterion(), batch_size=batch,
        end_when=Trigger.max_iteration(z["steps"]), mesh=mesh,
        parameter_sync="sharded")
    opt.set_optim_method(SGD(learning_rate=0.01, momentum=0.9))
    opt.set_train_summary(log_)
    # the optimizer's own step, compiled once, its text kept
    built = {}
    build = opt._build_sharded_step

    def build_once(*a, **k):
        step, spec, size = build(*a, **k)
        built["step"] = CompiledOnce(step)
        return built["step"], spec, size

    opt._build_sharded_step = build_once
    opt.optimize()
    mem = per_device()
    out = check_training("DistriOptimizer", model, before, log_, z["steps"],
                         ctx["dev"].platform)
    slots = shard_report(opt._live_slots)
    check_spread("DistriOptimizer optimizer state", slots["bytes_per_device"])
    if all(m["peak_bytes_in_use"] for m in mem):
        check_spread("DistriOptimizer device memory",
                     [m["peak_bytes_in_use"] for m in mem])
    step_collectives = collectives(built["step"].text)
    check(step_collectives, "no collective in the compiled sharded step")
    # the one-device counterpart: the same global batch as n_dev
    # micro-batches, so BatchNorm sees the per-shard statistics the
    # data-parallel step sees
    del opt
    gc.collect()
    l_model, l_before, l_log = run_local_optimizer(ctx, z, batch,
                                                   grad_accum=n_dev)
    check_training("LocalOptimizer", l_model, l_before, l_log, z["steps"],
                   ctx["dev"].platform)
    # the tolerance the CPU tests hold the bf16 wire to (test_parallel.py)
    np.testing.assert_allclose(out["losses"], l_log.losses, rtol=1e-2)

    return {"entry": 'parallel.DistriOptimizer(parameter_sync="sharded")',
            "mesh": {"data": n_dev}, "model": f"resnet{z['depth']}",
            "global_batch": batch, "steps": z["steps"], **out,
            "local_losses": [round(v, 5) for v in l_log.losses],
            "local_counterpart": f"LocalOptimizer, gradient accumulation "
                                 f"over {n_dev} micro-batches",
            "optimizer_state": slots,
            "collectives_in_compiled_step": step_collectives,
            "per_device_memory": mem}


def phase_tp_server(ctx):
    import jax
    import numpy as np

    from bigdl_tpu.parallel import Engine

    s = ctx["sizes"]["serve"]
    n_dev = len(jax.devices())
    model = build_lm(ctx)
    reqs = make_requests(ctx, s["tp_requests"])
    mesh = Engine.create_mesh([("model", n_dev)])
    rows_tp, seen_tp = serve(ctx, model, reqs, "smoke-tp",
                             max_pages=s["tp_pages"], mesh=mesh)
    mem = per_device()
    for name in ("params", "kv_pool"):
        check_spread(f"engine {name} on the model mesh",
                     seen_tp[name]["bytes_per_device"])
    check(seen_tp["collectives"]["decode_step"],
          "no collective in the compiled tensor-parallel decode step")
    gc.collect()
    rows_one, seen_one = serve(ctx, model, reqs, "smoke-one",
                               max_pages=s["tp_pages"])
    parity = {i: greedy_equivalent(model, reqs[i][0], a, b,
                                   f"request {i}: sharded vs unsharded engine")
              for i, (a, b) in enumerate(zip(rows_tp, rows_one))}
    return {"entry": 'serving.ContinuousBatchingEngine(mesh=("model", N))',
            "mesh": {"model": n_dev}, "model": ctx["sizes"]["lm"],
            "requests": [{"prompt": len(p), "new": n} for p, n in reqs],
            "greedy_parity_with_unsharded_engine": parity,
            "sharded": seen_tp, "unsharded": seen_one,
            "per_device_memory": mem}


# -------------------------------------------------------------------- main
def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--chips", type=int, default=1, choices=(1, 4),
                   help="4: run only the mesh paths and their one-device "
                        "counterparts (needs four chips)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--rehearse", action="store_true",
                   help="tiny sizes on whatever device jax has (the CPU "
                        "rehearsal); never prints ok: true")
    args = p.parse_args(argv)

    device = None
    try:
        sys.path.insert(0, HERE)
        import jax

        from bigdl_tpu.utils.compile_cache import enable_persistent_cache

        cache_dir = enable_persistent_cache()
        devs = jax.devices()
        dev = devs[0]
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": len(devs)}
        if dev.platform != "tpu" and not args.rehearse:
            raise RuntimeError(f"no TPU: jax found {dev.platform!r}")
        if len(devs) != args.chips and not args.rehearse:
            raise RuntimeError(
                f"--chips {args.chips} needs exactly {args.chips} device(s); "
                f"jax found {len(devs)}")
        ctx = {"dev": dev, "seed": args.seed, "rehearse": args.rehearse,
               "sizes": SIZES["tiny" if args.rehearse else "real"],
               "compiles": CompileLog(), "cache_dir": cache_dir}
        t0 = time.perf_counter()
        phase(ctx, "device", phase_device)
        if args.chips == 4:
            phase(ctx, "distri_trainer", phase_distri)
            phase(ctx, "tp_server", phase_tp_server)
        else:
            phase(ctx, "kernel", phase_kernel)
            phase(ctx, "trainer_perf", phase_trainer_perf)
            phase(ctx, "trainer_local", phase_trainer_local)
            phase(ctx, "server", phase_server)
        say({"phase": "total", "seconds": round(time.perf_counter() - t0, 3),
             "note": NOTE})
    except BaseException as e:
        # the verdict is the last line of stdout; the exception goes on
        # (the traceback to stderr, a non-zero exit code)
        say({"ok": False, "device": device,
             "error": f"{type(e).__name__}: {e}"[:2000]})
        raise
    if args.rehearse:
        say({"ok": False, "device": device,
             "error": "rehearsal at tiny sizes: every phase ran, nothing "
                      "was proven about the chip"})
        sys.exit(3)
    say({"ok": True, "device": device})


if __name__ == "__main__":
    main()
