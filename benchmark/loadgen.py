"""The one traffic generator. A mix is a data file under ``traffic/``; this
module turns it, a seed and a window length into the inputs of a run.

A mix keeps ONE schedule: arrival times, prompt and output lengths and which
requests share a system prompt are drawn once from the mix's own
``law_seed``, so every run of a cell offers the same work at the same
moments. ``--seed`` gives the token values (and, in the drivers, the
weights). A window of some tens of requests cannot carry a reshuffle: with
the order drawn from the seed, which long request falls near the window's
end moved ``serve_tok_per_s`` by 6 % (PERF.md). A mix that wants another
schedule is another file with another ``law_seed``.
"""

import math

import numpy as np


def _draw(law, n, rng):
    """n values of one length law, clipped to its [min, max]."""
    if law["law"] != "lognormal":
        raise ValueError(f"unknown length law {law['law']!r}")
    v = np.exp(math.log(law["median"]) + law["sigma"]
               * rng.standard_normal(n))
    return np.clip(np.rint(v), law.get("min", 1), law.get("max", 1 << 30)
                   ).astype(np.int64)


def _gaps(arrivals, n, rng):
    """n inter-arrival gaps with mean 1/rate."""
    rate = float(arrivals["rate_per_s"])
    kind = arrivals["process"]
    if kind == "poisson":
        g = rng.exponential(1.0 / rate, n)
    elif kind == "gamma":            # burstier than Poisson for cv > 1
        shape = 1.0 / float(arrivals["cv"]) ** 2
        g = rng.gamma(shape, 1.0 / (rate * shape), n)
    else:
        raise ValueError(f"unknown arrival process {kind!r}")
    # the same offered load in every window: the gaps fill it exactly
    return g * (n / rate) / g.sum()


def open_loop_requests(mix, seed, seconds, vocab):
    """The requests of a run: a list of dicts with ``due_s`` (offset from
    the window's start; negative inside the mix's ``lead_in_s``, which fills
    the engine before the window opens and is not measured), ``prompt``
    (int32 ids), ``new_tokens`` and ``shared`` (index of its system prompt,
    or -1)."""
    rate = float(mix["arrivals"]["rate_per_s"])
    lead_in = float(mix.get("lead_in_s", 0.0))
    n = max(1, int(round(rate * (seconds + lead_in))))
    fixed = np.random.RandomState(int(mix.get("law_seed", 0)))
    prompts = _draw(mix["prompt_tokens"], n, fixed)
    outputs = _draw(mix["output_tokens"], n, fixed)
    gaps = _gaps(mix["arrivals"], n, fixed)
    sp = mix.get("shared_prefix") or {"share": 0.0, "count": 0, "tokens": 0}
    n_shared = int(round(sp["share"] * n))
    shared = np.full(n, -1, np.int64)
    if n_shared:
        shared[:n_shared] = np.arange(n_shared) % sp["count"]
    # the mix's own order of the draws
    prompts, outputs = prompts[fixed.permutation(n)], outputs[fixed.permutation(n)]
    shared = shared[fixed.permutation(n)]
    # the run's seed: token values only
    rng = np.random.RandomState(int(seed) % (2 ** 32))
    due = np.cumsum(gaps) - gaps[0] * 0.5 - lead_in
    heads = [rng.randint(0, vocab, sp["tokens"]).astype(np.int32)
             for _ in range(sp["count"])]
    floor = sp["tokens"] + int(sp.get("min_own_tokens", 16))
    reqs = []
    for i in range(n):
        length = int(prompts[i])
        if shared[i] >= 0:
            # the system prompt counts inside the prompt's length
            length = max(length, floor)
            body = rng.randint(0, vocab, length - sp["tokens"]).astype(np.int32)
            prompt = np.concatenate([heads[shared[i]], body])
        else:
            prompt = rng.randint(0, vocab, length).astype(np.int32)
        reqs.append({"due_s": float(due[i]), "prompt": prompt,
                     "new_tokens": int(outputs[i]), "shared": int(shared[i])})
    return reqs


def describe_requests(reqs, seconds):
    """The plain record of the traffic drawn (a run's first output line)."""
    lead = sum(r["due_s"] < 0 for r in reqs)
    reqs = [r for r in reqs if r["due_s"] >= 0]
    p = sorted(len(r["prompt"]) for r in reqs)
    o = sorted(r["new_tokens"] for r in reqs)

    def q(xs):
        return [int(xs[int(f * (len(xs) - 1))]) for f in (0, .25, .5, .75, 1)]

    return {"traffic": "open_loop", "requests": len(reqs),
            "lead_in_requests": lead, "window_s": seconds,
            "offered_req_per_s": round(len(reqs) / seconds, 4),
            "prompt_tokens_min_q1_med_q3_max": q(p),
            "output_tokens_min_q1_med_q3_max": q(o),
            "offered_output_tok_per_s": round(sum(o) / seconds, 2),
            "share_with_system_prompt": round(
                sum(r["shared"] >= 0 for r in reqs) / len(reqs), 4)}


def synthetic_dataset(mix, seed):
    """Images and 1-based labels in host memory, from the seed: the
    reference perf harness's synthetic job."""
    rng = np.random.default_rng(int(seed))
    n = int(mix["samples"])
    h, w, c = mix["image"]
    x = rng.standard_normal((n, h, w, c), dtype=np.float32)
    y = rng.integers(1, int(mix["classes"]) + 1, size=n).astype(np.float32)
    return x, y


def whole_batches(x, y, batch):
    """The dataset's records in their own order as whole batches
    ``[(images, labels), ...]``: views of ``x`` and ``y``, nothing copied
    (what a mix with ``"prebuilt": true`` feeds; a remainder is left out)."""
    return [(x[i:i + batch], y[i:i + batch])
            for i in range(0, x.shape[0] - batch + 1, batch)]


def describe_dataset(mix, x, chips):
    return {"traffic": "synthetic_dataset", "samples": int(x.shape[0]),
            "image": list(x.shape[1:]), "classes": int(mix["classes"]),
            "batch": int(mix["batch_per_chip"]) * chips, "chips": chips,
            "prebuilt": bool(mix.get("prebuilt", False)),
            "batches_per_epoch": int(x.shape[0])
            // (int(mix["batch_per_chip"]) * chips),
            "host_bytes": int(x.nbytes)}
