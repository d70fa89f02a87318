"""TPU perf sweep: run the perf harness over a config matrix and print a
table + JSON lines. Used to pick the bench.py defaults (batch/format) on
real hardware; each config runs few iterations so a sweep fits one short
chip call.

Run: bigdl-tpu-sweep [--quick]   (or python scripts/tpu_sweep.py)
"""

import argparse
import json
import sys
import time


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--quick", action="store_true", help="2 configs only")
    p.add_argument("--iters", type=int, default=10)
    p.add_argument("--out", default="tpu_sweep.jsonl")
    args = p.parse_args(argv)

    from bigdl_tpu.utils.compile_cache import enable_persistent_cache

    enable_persistent_cache()

    import jax
    import jax.numpy as jnp

    from bigdl_tpu.models.perf import run_perf

    dev = jax.devices()[0]
    print(f"device: {dev.device_kind}", file=sys.stderr)

    if dev.platform == "cpu":  # smoke-test shapes only
        print("[sweep] CPU backend: smoke config only (lenet5, iters<=2); "
              "--iters/--quick apply on TPU", file=sys.stderr)
        configs = [dict(model="lenet5", batch=8, format="NCHW")]
        args.iters = min(args.iters, 2)
    else:
        configs = [
            dict(model="resnet50", batch=256, format="NHWC"),
            dict(model="resnet50", batch=512, format="NHWC"),
            dict(model="resnet50", batch=256, format="NCHW"),
            dict(model="resnet50", batch=128, format="NHWC"),
            dict(model="transformer", batch=8, format="NCHW"),
        ]
        if args.quick:
            configs = configs[:2]

    results = []
    with open(args.out, "a") as fh:
        for cfg in configs:
            t0 = time.perf_counter()
            cfg = dict(cfg, device=str(getattr(dev, "device_kind",
                                               dev.platform)))
            try:
                s = run_perf(cfg["model"], batch_size=cfg["batch"],
                             iterations=args.iters, dtype=jnp.bfloat16,
                             format=cfg["format"], master_f32=True,
                             log=lambda *a, **k: print(*a, file=sys.stderr))
                row = {**cfg, "records_per_sec": s["records_per_sec"],
                       "ms_per_iter": s["ms_per_iter"],
                       "compile_s": s["warmup_s"], "iters": args.iters,
                       "wall_s": round(time.perf_counter() - t0, 1)}
            except Exception as e:
                row = {**cfg, "error": f"{type(e).__name__}: {e}"}
            results.append(row)
            fh.write(json.dumps(row) + "\n")
            fh.flush()
            print(json.dumps(row), file=sys.stderr)

    print(json.dumps(results))


if __name__ == "__main__":
    main()
