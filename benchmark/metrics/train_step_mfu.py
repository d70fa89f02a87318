"""Kernels (XLA's convolutions): operations forward and backward require for
the chip's share of the batch (``benchmark/counts.py``, nothing recomputed
counted) over the compute peak, over the step's measured device time."""
from benchmark import counts
from benchmark.reduce_trace import program_median_ms


def value(run, trace):
    ms = program_median_ms(trace, run["programs"].get("train_step", []))
    if not ms:
        return None
    z = run["sizes"]
    flops = counts.resnet50_train_step_flops(
        run["batch_per_chip"], z["image"], z["classes"])
    return 100.0 * flops / run["peaks"]["flops_per_s"] / (ms / 1e3)
