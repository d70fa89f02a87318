"""Synthetic-data training throughput harness.

Reference: models/utils/DistriOptimizerPerf.scala:32-140 and
LocalOptimizerPerf.scala — feed ImageNet-shaped random batches through a
model by name and report records/sec. TPU-native: one jitted train step,
device-resident synthetic batch (no host↔HBM transfer in the timed loop),
`block_until_ready` fencing around the timed region.
"""

from __future__ import annotations

import json
import os
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu import nn
from bigdl_tpu.nn.module import Module
from bigdl_tpu.optim.optim_method import SGD
from bigdl_tpu.optim.optimizer import make_train_step
from bigdl_tpu.utils import random as bt_random


def _cast_floating(tree, dtype):
    """Cast every floating leaf to ``dtype`` (ints/bools untouched)."""
    return jax.tree.map(
        lambda a: a.astype(dtype)
        if jnp.issubdtype(a.dtype, jnp.floating) else a, tree)


def build_model(name: str, class_num: int = 1000, format: str = "NCHW"):
    """Model + (input shape sans batch, target kind) by name
    (≙ DistriOptimizerPerf's --model flag). ``format="NHWC"`` builds the
    channels-last variant (TPU-preferred) where the model supports it."""
    from bigdl_tpu.models.inception import InceptionV1NoAuxClassifier
    from bigdl_tpu.models.lenet import LeNet5
    from bigdl_tpu.models.resnet import DatasetType, ResNet
    from bigdl_tpu.models.vgg import Vgg16, VggForCifar10

    name = name.lower()
    if name == "lenet5":
        return LeNet5(10), (28, 28), 10
    if name == "vgg16":
        return Vgg16(class_num), (3, 224, 224), class_num
    if name == "vggcifar":
        return VggForCifar10(10), (3, 32, 32), 10
    if name in ("inception_v1", "inception"):
        return InceptionV1NoAuxClassifier(class_num), (3, 224, 224), class_num
    if name.startswith("mobilenet"):
        from bigdl_tpu.models.mobilenet import MobileNetV1

        # accepted: mobilenet, mobilenet_v1, mobilenet_<width> (e.g. _0.5)
        suffix = name[len("mobilenet"):].lstrip("_")
        if suffix in ("", "v1"):
            width = 1.0
        else:
            try:
                width = float(suffix)
            except ValueError:
                raise ValueError(
                    f"unknown mobilenet variant {name!r} (only V1 exists "
                    "here; use mobilenet, mobilenet_v1, or mobilenet_<width>)")
        shape = (224, 224, 3) if format == "NHWC" else (3, 224, 224)
        return (MobileNetV1(class_num, width=width, format=format),
                shape, class_num)
    if name.startswith("resnet"):
        depth = int(name[len("resnet"):] or 50)
        shape = (224, 224, 3) if format == "NHWC" else (3, 224, 224)
        return (ResNet(class_num, {"depth": depth, "dataSet": DatasetType.ImageNet,
                                   "format": format}),
                shape, class_num)
    raise ValueError(f"unknown perf model {name!r}")


def _compile_step(ts, params, buffers, slots, x, y, lrs):
    """The timed step as ONE ahead-of-time executable, plus XLA's price
    for it. The executable is what the loop then calls, so the count is
    read from the program that runs, costs no second compile, and a
    changed shape raises instead of recompiling inside the window. (A
    lowering priced before compilation reports nothing for a TPU.)"""
    from bigdl_tpu.observability.costmodel import executable_cost

    compiled = jax.jit(ts.step, donate_argnums=(0, 1, 2)).lower(
        params, buffers, slots, x, y, lrs, jax.random.PRNGKey(0)).compile()
    return compiled, executable_cost(compiled)


def _transformer_perf(batch_size, iterations, warmup, dtype, log,
                      seq_len=1024, vocab=32000, embed_dim=512, layers=8,
                      heads=8, use_flash=True, master_f32=True,
                      profile_dir=None):
    """Tokens/sec on the long-context flagship (TransformerLM + pallas
    flash attention). Separate from run_perf because the input is int
    tokens and the natural unit is tokens/sec, not records/sec."""
    from bigdl_tpu.models.transformer import TransformerLM
    from bigdl_tpu.nn import CrossEntropyCriterion

    on_cpu = jax.devices()[0].platform == "cpu"
    model = TransformerLM(vocab, embed_dim=embed_dim, num_heads=heads,
                          num_layers=layers, max_len=seq_len,
                          use_flash=use_flash and not on_cpu)

    class _LMLoss:
        # next-token CE over the flattened time axis (labels 1-based)
        def forward(self, logits, ids):
            lg = logits[:, :-1].reshape(-1, vocab)
            tg = ids[:, 1:].reshape(-1) + 1
            return CrossEntropyCriterion().forward(lg, tg)

    method = SGD(learning_rate=0.01)
    ts = make_train_step(model, _LMLoss(), method,
                         compute_dtype=dtype if master_f32 else None)
    params = jax.tree.map(jnp.copy, model.params_dict())
    buffers = jax.tree.map(jnp.copy, model.buffers_dict())
    if not master_f32:  # store params directly at the compute dtype
        params = _cast_floating(params, dtype)
        buffers = _cast_floating(buffers, dtype)
    slots = ts.init_slots(params)
    lrs = ts.current_lrs()
    ids = jax.random.randint(jax.random.PRNGKey(0), (batch_size, seq_len),
                             0, vocab)
    t0 = time.perf_counter()
    step, cost = _compile_step(ts, params, buffers, slots, ids, ids, lrs)
    for _ in range(max(1, warmup)):
        loss, params, buffers, slots = step(params, buffers, slots, ids, ids,
                                            lrs, bt_random.next_key())
    jax.block_until_ready(loss)
    compile_s = time.perf_counter() - t0
    import contextlib
    prof = (jax.profiler.trace(profile_dir) if profile_dir
            else contextlib.nullcontext())
    with prof:
        t0 = time.perf_counter()
        for _ in range(iterations):
            loss, params, buffers, slots = step(params, buffers, slots,
                                                ids, ids, lrs,
                                                bt_random.next_key())
        jax.block_until_ready(loss)
        elapsed = time.perf_counter() - t0
    loss_v = float(loss)
    tok_per_sec = batch_size * seq_len * iterations / elapsed
    s = {"model": "transformer_lm", "batch_size": batch_size,
         "seq_len": seq_len, "iterations": iterations,
         "warmup_s": round(compile_s, 3), "time_s": round(elapsed, 4),
         "records_per_sec": round(tok_per_sec, 2),
         "ms_per_iter": round(1000.0 * elapsed / iterations, 3),
         "loss": loss_v}
    if cost is not None:
        s["flops_per_iter"] = cost["flops"]
        s["bytes_per_iter"] = cost["bytes"]
        s["cost_source"] = cost["source"]
    log(f"[perf] transformer_lm batch={batch_size} seq={seq_len}: "
        f"{tok_per_sec:.0f} tokens/s ({s['ms_per_iter']:.1f} ms/iter)")
    return s


def run_perf(model_name: str = None, batch_size: int = 32,
             iterations: int = 20, warmup: int = 3,
             dtype=jnp.float32, criterion=None,
             model: Optional[Module] = None, input_shape=None,
             class_num: int = 1000, log=print, format: str = "NCHW",
             master_f32: bool = False, profile_dir: Optional[str] = None) -> dict:
    """Time a jitted train step on synthetic data; returns a summary dict
    with records/sec (the reference's per-iteration Throughput line,
    optim/DistriOptimizer.scala:387-393).

    ``master_f32=True`` keeps f32 master params and casts to ``dtype`` once
    inside the step (mixed precision); otherwise params are stored in
    ``dtype`` directly. ``profile_dir`` captures a jax.profiler trace of the
    timed region."""
    if model is None:
        model_name = model_name or "resnet50"
        if model_name in ("transformer", "transformer_lm"):
            if criterion is not None:
                raise ValueError(
                    "the transformer bench fixes its own next-token CE loss; "
                    "custom criterion is not supported")
            # format applies to conv models only; tokens have no layout
            if format not in ("NCHW", None):
                log(f"[perf] note: format={format!r} ignored for transformer")
            return _transformer_perf(batch_size, iterations, warmup, dtype,
                                     log, master_f32=master_f32,
                                     profile_dir=profile_dir)
        model, input_shape, class_num = build_model(model_name, class_num, format=format)
    elif input_shape is None:
        raise ValueError("input_shape is required when passing a custom model")
    else:
        model_name = model_name or "custom"
    if criterion is None:
        # ResNet's ImageNet head emits raw logits (trained with
        # CrossEntropyCriterion in the reference, models/resnet/TrainImageNet.scala);
        # the other zoo models end in LogSoftMax → ClassNLL.
        if model_name.startswith("resnet"):
            criterion = nn.CrossEntropyCriterion()
        else:
            criterion = nn.ClassNLLCriterion()

    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (batch_size,) + tuple(input_shape), dtype)
    y = jnp.ones((batch_size,), jnp.int32)  # 1-based labels (Appendix B.1)

    method = SGD(learning_rate=0.01)
    ts = make_train_step(model, criterion, method,
                         compute_dtype=dtype if master_f32 else None)
    # copy params out of the module before donation — step() donates its
    # buffers, which must not invalidate the caller's live model arrays
    params = jax.tree.map(jnp.copy, model.params_dict())
    buffers = jax.tree.map(jnp.copy, model.buffers_dict())
    if not master_f32:
        params = _cast_floating(params, dtype)
        buffers = _cast_floating(buffers, dtype)
    slots = ts.init_slots(params)
    lrs = ts.current_lrs()

    t0 = time.perf_counter()
    step, cost = _compile_step(ts, params, buffers, slots, x, y, lrs)
    for _ in range(max(1, warmup)):
        loss, params, buffers, slots = step(params, buffers, slots, x, y, lrs,
                                            bt_random.next_key())
    jax.block_until_ready(loss)
    compile_s = time.perf_counter() - t0
    warmup_loss = float(loss)

    import contextlib
    prof = (jax.profiler.trace(profile_dir) if profile_dir
            else contextlib.nullcontext())
    with prof:
        t0 = time.perf_counter()
        for _ in range(iterations):
            loss, params, buffers, slots = step(params, buffers, slots, x, y, lrs,
                                                bt_random.next_key())
        jax.block_until_ready(loss)
        elapsed = time.perf_counter() - t0
    loss_v = float(loss)

    rec_per_sec = batch_size * iterations / elapsed
    summary = {
        "model": model_name,
        "batch_size": batch_size,
        "iterations": iterations,
        "warmup_s": round(compile_s, 3),
        "time_s": round(elapsed, 4),
        "records_per_sec": round(rec_per_sec, 2),
        "ms_per_iter": round(1000.0 * elapsed / iterations, 3),
        "warmup_loss": warmup_loss,
        "loss": loss_v,
        "device_platform": next(iter(loss.devices())).platform,
    }
    if cost is not None:
        summary["flops_per_iter"] = cost["flops"]
        summary["bytes_per_iter"] = cost["bytes"]
        summary["cost_source"] = cost["source"]
    log(f"[perf] {model_name} batch={batch_size}: "
        f"{rec_per_sec:.1f} records/s ({summary['ms_per_iter']:.1f} ms/iter)")
    return summary


def run_decode_perf(batch_size: int = 8, prompt_len: int = 128,
                    new_tokens: int = 128, vocab: int = 32000,
                    embed_dim: int = 512, layers: int = 8, heads: int = 8,
                    num_kv_heads: Optional[int] = None,
                    use_rope: bool = True, dtype=jnp.bfloat16,
                    int8: bool = False, speculative: int = 0,
                    spec_gamma: int = 4, spec_int8_draft: bool = False,
                    profile_dir: Optional[str] = None, log=print) -> dict:
    """Serving-side throughput: KV-cache autoregressive decode tokens/sec.
    generate() keeps its jitted prefill/step per model instance, so the
    first call compiles and the timed second call is pure decode."""
    from bigdl_tpu.models.transformer import TransformerLM

    on_cpu = jax.devices()[0].platform == "cpu"
    if on_cpu:  # keep the CPU smoke tractable (clamp EVERY knob, so any
        # documented TPU invocation still runs as a smoke)
        vocab, embed_dim, layers, heads = 256, 64, 2, 4
        prompt_len, new_tokens = min(prompt_len, 16), min(new_tokens, 16)
        if speculative:
            speculative = min(speculative, layers - 1)
    if (speculative or spec_int8_draft) and int8:
        raise ValueError("speculative modes build their draft from the "
                         "float target; combine with --int8 is not "
                         "supported")
    if speculative and spec_int8_draft:
        raise ValueError("--speculative K and --speculative-int8 are "
                         "alternative draft choices; pick one")
    if speculative and speculative >= layers:
        raise ValueError(f"--speculative draft layers ({speculative}) must "
                         f"be < target layers ({layers})")
    spec = bool(speculative or spec_int8_draft)
    max_len = prompt_len + new_tokens + (spec_gamma if spec else 0)
    model = TransformerLM(vocab, embed_dim=embed_dim, num_heads=heads,
                          num_layers=layers, num_kv_heads=num_kv_heads,
                          max_len=max_len, use_rope=use_rope)
    model.evaluate()
    if dtype != jnp.float32:
        # bf16 params ALSO give a bf16 KV cache (generate derives the
        # cache dtype from the params) — the bandwidth that decode is
        # actually bound by
        model.load_params_dict(_cast_floating(model.params_dict(), dtype))
    draft = None
    if speculative:
        # truncated-depth draft sharing the target's first k blocks and
        # embeddings (early-exit style): real acceptance rates without a
        # separately trained draft
        draft = TransformerLM(vocab, embed_dim=embed_dim, num_heads=heads,
                              num_layers=speculative,
                              num_kv_heads=num_kv_heads,
                              max_len=max_len, use_rope=use_rope)
        draft.evaluate()
        tp = model.params_dict()
        draft.load_params_dict({k: tp[k] for k in draft.params_dict()})
    elif spec_int8_draft:
        # int8 clone of the FULL target as the draft: near-100% greedy
        # acceptance (int8 rarely flips the argmax), so the measured
        # speedup isolates the int8 weight-traffic saving per proposal
        from bigdl_tpu.nn.quantized import Quantizer

        draft = Quantizer.quantize(model)
        draft.evaluate()
    if int8:
        # post-training int8: every Linear swaps to the int8 kernel —
        # weight HBM traffic halves vs bf16 (the term decode is bound
        # by); token parity vs float is pinned in tests/test_quantized.py
        from bigdl_tpu.nn.quantized import Quantizer

        model = Quantizer.quantize(model)
        model.evaluate()
    prompt = jax.random.randint(jax.random.PRNGKey(0),
                                (batch_size, prompt_len), 0, vocab)
    t0 = time.perf_counter()
    out = model.generate(prompt, new_tokens)
    jax.block_until_ready(out)
    warm_s = time.perf_counter() - t0  # compiles prefill + decode scan
    import contextlib

    prof = (jax.profiler.trace(profile_dir) if profile_dir
            else contextlib.nullcontext())
    with prof:
        t0 = time.perf_counter()
        out = model.generate(prompt, new_tokens)
        jax.block_until_ready(out)
        elapsed = time.perf_counter() - t0
    tok_per_sec = batch_size * new_tokens / elapsed
    # prefill-side throughput: generate(prompt, 1, host_loop=True) runs
    # ONLY the batched prefill (token 1 samples straight from the prefill
    # logits, zero decode steps on either path; host_loop avoids
    # compiling a fresh n=1 scan program, which would turn this timing
    # into a compile benchmark); max_len pins the cache to the warm
    # call's shapes so the prefill jit is a cache hit, not a recompile
    t0 = time.perf_counter()
    jax.block_until_ready(model.generate(prompt, 1,
                                         max_len=prompt_len + new_tokens,
                                         host_loop=True))
    prefill_s = time.perf_counter() - t0
    s = {"model": "transformer_lm_decode", "int8": bool(int8),
         "batch_size": batch_size,
         "prompt_len": prompt_len, "new_tokens": new_tokens,
         "num_kv_heads": num_kv_heads or heads,
         "warmup_s": round(warm_s, 3), "time_s": round(elapsed, 4),
         "decode_tokens_per_sec": round(tok_per_sec, 2),
         "prefill_tokens_per_sec": round(
             batch_size * prompt_len / max(prefill_s, 1e-9), 1),
         "ms_per_token": round(1000.0 * elapsed
                               / (batch_size * new_tokens), 3)}
    if draft is not None:
        # same tokens as plain greedy (exactness tested); what changes is
        # how many target forwards it takes — report the measured ratio
        jax.block_until_ready(model.speculative_generate(
            prompt, new_tokens, draft=draft, gamma=spec_gamma))  # compile
        t0 = time.perf_counter()
        _, st = model.speculative_generate(prompt, new_tokens, draft=draft,
                                           gamma=spec_gamma,
                                           return_stats=True)
        spec_s = time.perf_counter() - t0
        s.update({
            "speculative_draft_layers": speculative or "int8",
            "spec_gamma": spec_gamma,
            "spec_tokens_per_sec": round(
                batch_size * new_tokens / spec_s, 2),
            "spec_rounds": st["rounds"],
            "spec_accept_rate": round(st["accept_rate"], 3),
            "spec_vs_plain": round(elapsed / spec_s, 3),
        })
    log(f"[perf] decode batch={batch_size} prompt={prompt_len} "
        f"new={new_tokens}: {tok_per_sec:.0f} tokens/s decode, "
        f"{s['prefill_tokens_per_sec']:.0f} tokens/s prefill"
        + (f"; speculative {s['spec_tokens_per_sec']:.0f} tokens/s "
           f"({s['spec_vs_plain']:.2f}x, accept "
           f"{s['spec_accept_rate']:.0%})" if draft is not None else ""))
    return s


def run_input_pipeline_perf(batch_size: int = 64, n_records: int = 512,
                            image: int = 256, crop: int = 224,
                            depths=(0, 2, 4), shards: int = 4,
                            native_modes=(True, False), log=print) -> list:
    """Host input-pipeline throughput: records/sec through
    ``RecordFileDataSet`` -> vision augment chain (RandomCrop + HFlip +
    ChannelNormalize, the ImageNet train path) -> ``SampleToMiniBatch`` ->
    sharded H2D staging, with and without the native C++ reader pool and
    at prefetch depths {0, 2, 4}. No model step runs — this measures the
    FEED side only, so compare records/sec against the device's measured
    imgs/sec demand (bench.py) to decide whether the host can keep a chip
    fed. Engineering intent ≙ ref: dataset/image/MTLabeledBGRImgToBatch
    .scala:1 (the reference's multithreaded batch assembly)."""
    import tempfile

    import bigdl_tpu.native as native_mod
    from bigdl_tpu.dataset.prefetch import prefetch
    from bigdl_tpu.dataset.records import (RecordFileDataSet,
                                           write_record_shards)
    from bigdl_tpu.dataset.sample import Sample
    from bigdl_tpu.dataset.transformer import SampleToMiniBatch
    from bigdl_tpu.parallel.engine import Engine
    from bigdl_tpu.transform.vision import (ChannelNormalize, HFlip,
                                            ImageFeature, RandomCrop)
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = Engine.default_mesh()
    sharding = (NamedSharding(mesh, P("data"))
                if "data" in mesh.axis_names else None)
    n_batches = n_records // batch_size
    n_used = n_batches * batch_size
    results = []
    rng0 = np.random.RandomState(0)
    with tempfile.TemporaryDirectory() as d:

        def gen():
            for i in range(n_records):
                img = rng0.randint(0, 255, (image, image, 3), np.uint8)
                yield Sample(img, np.array([1.0 + (i % 1000)], np.float32))

        write_record_shards(gen(), d, num_shards=shards)

        MEANS = [123.68, 116.779, 103.939]
        STDS = [58.393, 57.12, 57.375]
        composed_aug = (RandomCrop(crop, crop) >> HFlip()
                        >> ChannelNormalize(MEANS, STDS))

        def sample_stream(aug):
            ds = RecordFileDataSet(d, num_shards=1, shard_id=0)
            src = ds.data(train=True)  # infinite shuffled walk
            feats = (ImageFeature(next(src).feature(), label=None,
                                  preserve_dtype=True)
                     for _ in range(n_used))
            for f in aug(feats):
                yield Sample(f.image(), np.float32(1.0))

        def to_device(mb):
            x = np.asarray(mb.get_input())
            if sharding is not None and x.shape[0] % mesh.shape["data"] == 0:
                return jax.device_put(x, sharding)
            return jnp.asarray(x)

        def run_config(aug, use_native, depth, fused):
            batches = SampleToMiniBatch(batch_size)(sample_stream(aug))
            it = (prefetch(batches, buffer_size=depth,
                           transfer=to_device) if depth > 0
                  else (to_device(b) for b in batches))
            t0 = time.perf_counter()
            seen = 0
            for x in it:
                x.block_until_ready()
                seen += x.shape[0]
            elapsed = time.perf_counter() - t0
            row = {"mode": "input_pipeline",
                   "native_reader": bool(use_native),
                   "fused_augment": bool(fused),
                   "prefetch_depth": depth,
                   "batch_size": batch_size,
                   "records": seen,
                   "image": image, "crop": crop,
                   "records_per_sec": round(seen / elapsed, 1),
                   "time_s": round(elapsed, 3)}
            results.append(row)
            log(f"[pipeline] native={use_native} fused={fused} "
                f"depth={depth}: {row['records_per_sec']:.0f} records/s")

        for use_native in native_modes:
            if use_native and not native_mod.native_available():
                log("[pipeline] native reader unavailable; skipping")
                continue
            orig_get_lib = native_mod.get_lib
            if not use_native:
                native_mod.get_lib = lambda: None
            try:
                for depth in depths:
                    run_config(composed_aug, use_native, depth, fused=False)
            finally:
                native_mod.get_lib = orig_get_lib

        # the fused one-pass augment (native/augment.cc): same semantics
        # as the composed chain (flip_prob=1.0 ≙ the always-flip HFlip),
        # one pixel walk instead of three
        if native_mod.fused_augment_available():
            from bigdl_tpu.transform.vision import FusedCropFlipNormalize

            fused_aug = FusedCropFlipNormalize(crop, crop, MEANS, STDS,
                                               flip_prob=1.0)
            for depth in depths:
                run_config(fused_aug, True, depth, fused=True)
            # multithreaded apply (plans stay serial/deterministic): the
            # ctypes kernel drops the GIL, so this row scales with host
            # cores — flat on a 1-core box, the point on a real TPU host
            workers = min(4, os.cpu_count() or 1)
            if workers > 1:
                par_aug = FusedCropFlipNormalize(crop, crop, MEANS, STDS,
                                                 flip_prob=1.0,
                                                 workers=workers)
                run_config(par_aug, True, max(depths), fused=True)
                results[-1]["augment_workers"] = workers
        else:
            log("[pipeline] fused augment unavailable; skipping")
    return results


def _append_rows_to_history(rows) -> None:
    """Append result rows to the bench trend file — cwd-relative (a wheel
    install must not litter the venv), `BIGDL_BENCH_HISTORY` overrides
    (same env contract as bench.py's writer)."""
    hist = (os.environ.get("BIGDL_BENCH_HISTORY")
            or os.path.join(os.getcwd(), "bench_history.jsonl"))
    try:
        with open(hist, "a") as f:
            for r in rows:
                f.write(json.dumps(dict(r, ts=time.time())) + "\n")
    except OSError:
        pass


def main(argv=None):
    import argparse

    from bigdl_tpu.utils.compile_cache import enable_persistent_cache

    enable_persistent_cache()

    p = argparse.ArgumentParser(description="bigdl_tpu training perf (≙ DistriOptimizerPerf)")
    p.add_argument("--model", default="resnet50")
    p.add_argument("--batch-size", type=int, default=32)
    p.add_argument("--iterations", type=int, default=20)
    p.add_argument("--dtype", default="float32", choices=["float32", "bfloat16"])
    p.add_argument("--format", default="NCHW", choices=["NCHW", "NHWC"])
    p.add_argument("--master-f32", action="store_true",
                   help="f32 master params + compute-dtype cast in-step")
    p.add_argument("--profile", default=None, metavar="DIR",
                   help="capture a jax.profiler trace of the timed loop")
    p.add_argument("--decode", action="store_true",
                   help="measure KV-cache decode tokens/sec instead of "
                        "training throughput (transformer only)")
    p.add_argument("--input-pipeline", action="store_true",
                   help="measure host feed records/sec (records -> "
                        "augments -> minibatch -> sharded H2D), no model")
    p.add_argument("--int8", action="store_true",
                   help="--decode: post-training int8 weights (halved "
                        "weight HBM traffic; token parity tested)")
    p.add_argument("--records", type=int, default=512,
                   help="--input-pipeline: records per config")
    p.add_argument("--prompt-len", type=int, default=128,
                   help="--decode: prompt length")
    p.add_argument("--new-tokens", type=int, default=128,
                   help="--decode: generated tokens per pass")
    p.add_argument("--speculative", type=int, default=0, metavar="K",
                   help="--decode: also time greedy speculative decoding "
                        "with a K-layer truncated-depth draft (exact "
                        "tokens; reports accept rate + speedup)")
    p.add_argument("--spec-gamma", type=int, default=4,
                   help="--speculative: draft proposals per round")
    p.add_argument("--speculative-int8", action="store_true",
                   help="--decode: speculative decoding with the int8 "
                        "clone of the target as the draft (near-100%% "
                        "greedy acceptance; isolates the int8 "
                        "weight-traffic saving per proposal)")
    args = p.parse_args(argv)
    dtype = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    if args.input_pipeline:
        rows = run_input_pipeline_perf(batch_size=args.batch_size,
                                       n_records=args.records)
        _append_rows_to_history(rows)
        print(json.dumps(rows))
        return
    if args.decode:
        if args.model not in ("resnet50", "transformer", "transformer_lm"):
            p.error("--decode measures the transformer LM; --model does "
                    "not apply")
        if args.master_f32 or args.format != "NCHW":
            p.error("--decode takes --batch-size/--dtype/--prompt-len/"
                    "--new-tokens/--int8/--profile only")
        if args.new_tokens < 1 or args.prompt_len < 1:
            p.error("--prompt-len/--new-tokens must be >= 1")
        s = run_decode_perf(batch_size=args.batch_size, dtype=dtype,
                            prompt_len=args.prompt_len,
                            new_tokens=args.new_tokens,
                            int8=args.int8, speculative=args.speculative,
                            spec_gamma=args.spec_gamma,
                            spec_int8_draft=args.speculative_int8,
                            profile_dir=args.profile)
        s["device"] = str(getattr(jax.devices()[0], "device_kind",
                                  jax.devices()[0].platform))
        _append_rows_to_history([s])
        print(json.dumps(s))
        return
    run_perf(args.model, args.batch_size, args.iterations, dtype=dtype,
             format=args.format, master_f32=args.master_f32,
             profile_dir=args.profile)


if __name__ == "__main__":
    main()
