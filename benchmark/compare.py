"""The comparisons that decide ``correct``. Each returns rows
``{"name", "value", "limit", "ok"}``; a run prints every row and is correct
only if all hold. The limits are the configuration's (``check`` in its file);
PERF.md gives the readings each was set from."""

import numpy as np


def row(name, value, limit, exact=False):
    value = float(value)
    ok = bool(np.isfinite(value) and (value == limit if exact
                                      else value <= limit))
    return {"name": name, "value": value, "limit": limit, "ok": ok}


# ----------------------------------------------------------------- serving
def served_token_gaps(logits, row_ids, prompt_len):
    """For each served token of one request: how far its reference logit
    lies below the reference's best at that position, relative to the
    logits' scale there. ``logits`` (time, vocab) from the reference's
    forward over ``row_ids`` = prompt + served tokens."""
    logits = np.asarray(logits, np.float32)
    pos = np.arange(prompt_len, len(row_ids))          # served positions
    at = logits[pos - 1]                               # each one's predictor
    top = at.max(axis=-1)
    got = at[np.arange(len(pos)), np.asarray(row_ids)[pos]]
    scale = np.abs(at).max(axis=-1)
    return (top - got) / scale


def margins(logits, prompt_len, n_rows):
    """The reference's own top-1 minus top-2 at each served position,
    relative to the logits' scale: how much room a rounding error has."""
    at = np.asarray(logits, np.float32)[np.arange(prompt_len, n_rows) - 1]
    top2 = np.partition(at, -2, axis=-1)[:, -2:]
    return (top2[:, 1] - top2[:, 0]) / np.abs(at).max(axis=-1)


def argmax_gaps(logits, other_logits, prompt_len, n_rows):
    """A control's reading, no decoding needed: at each served position,
    the gap (under ``logits``, the reference) of the token that
    ``other_logits`` (a lower precision) puts first."""
    logits = np.asarray(logits, np.float32)
    pos = np.arange(prompt_len, n_rows)
    at = logits[pos - 1]
    pick = np.asarray(other_logits)[pos - 1].argmax(axis=-1)
    return (at.max(axis=-1) - at[np.arange(len(pos)), pick]) \
        / np.abs(at).max(axis=-1)


def logit_error_rel_rms(logits, ref_logits, spans):
    """How far ``logits`` lie from the reference's over every position of
    every row (up to its length) and the whole vocabulary: the root mean
    square of the difference over that of the reference's logits about
    their mean at each position. Millions of numbers go into it, so it is
    steady from seed to seed where a widest gap swings."""
    err = scale = 0.0
    for i, (_, n) in enumerate(spans):
        ref = np.asarray(ref_logits[i, :n], np.float64)
        err += float(np.square(np.asarray(logits[i, :n], np.float64)
                               - ref).sum())
        scale += float(np.square(ref - ref.mean(axis=-1, keepdims=True)).sum())
    return (err / scale) ** 0.5 if scale else float("inf")


def reference_facts(logits, rows, spans):
    """What the reference says of the compared tokens (a log line)."""
    room = np.concatenate([margins(logits[i], a, b)
                           for i, (a, b) in enumerate(spans)] or [np.zeros(1)])
    gaps = np.concatenate([served_token_gaps(logits[i], rows[i, :b], a)
                           for i, (a, b) in enumerate(spans)] or [np.zeros(1)])
    return {"top2_margin_rel_p10_p50": [float(np.percentile(room, 10)),
                                        float(np.percentile(room, 50))],
            "served_is_reference_argmax_share": float((gaps == 0).mean()),
            "distinct_served_tokens_share": float(np.mean(
                [len(set(rows[i, a:b].tolist())) / (b - a)
                 for i, (a, b) in enumerate(spans)] or [0]))}


def serving_rows(ref_logits, paged_logits, rows, spans, streamed_equal,
                 compiles_in_window, limits):
    """``ref_logits``: the plain reference over ``rows`` (prompt then served
    tokens, ``spans`` their lengths); ``paged_logits``: the served model's
    own paged-prefill logits over the same rows. The served tokens are held
    to both (a wrong page, head or position shows under either; under the
    model's own logits only rounding order parts them), the model's logits
    to the reference's (the precision the model is served in)."""
    def widest_and_mean(logits):
        gaps = np.concatenate([served_token_gaps(logits[i], rows[i, :b], a)
                               for i, (a, b) in enumerate(spans)]
                              or [np.full(1, np.inf)])
        return gaps.max(), gaps.mean()

    ref_max, ref_mean = widest_and_mean(ref_logits)
    own_max, _ = widest_and_mean(paged_logits)
    return [
        row("served_token_gap_max_rel", ref_max,
            limits["served_token_gap_max_rel"]),
        row("served_token_gap_mean_rel", ref_mean,
            limits["served_token_gap_mean_rel"]),
        row("served_token_gap_under_own_logits_max_rel", own_max,
            limits["served_token_gap_under_own_logits_max_rel"]),
        row("own_logits_error_rel_rms",
            logit_error_rel_rms(paged_logits, ref_logits, spans),
            limits["own_logits_error_rel_rms"]),
        row("streamed_tokens_differing_from_row", 0 if streamed_equal else 1,
            0, exact=True),
        row("compiles_in_window", compiles_in_window, 0, exact=True),
    ]


# ---------------------------------------------------------------- training
def leaf_norms(tree):
    return {k: float(np.linalg.norm(np.asarray(v, np.float64).ravel()))
            for k, v in tree.items()}


def worst_leaf_gap(prog_norms, ref_norms):
    """The widest gap, over leaves, between the program's norm and the
    reference's (the gap of the norms, not the norm of the difference),
    against the reference's norm of that leaf or of its median leaf,
    whichever is larger: some gradients are all but zero."""
    med = float(np.median(list(ref_norms.values())))
    worst, where = 0.0, None
    for k, r in ref_norms.items():
        gap = abs(prog_norms[k] - r) / max(r, med, 1e-30)
        if not np.isfinite(gap):
            return float("inf"), k
        if gap > worst:
            worst, where = gap, k
    return worst, where


def training_rows(prog, ref, compiles_in_window, limits):
    """``prog`` / ``ref``: ``losses`` (first steps), ``grad_norms`` and
    ``delta_norms`` (leaf -> norm)."""
    rows = []
    for i, (a, b) in enumerate(zip(prog["losses"], ref["losses"])):
        rows.append(row(f"loss_step{i + 1}_rel_gap", abs(a - b) / abs(b),
                        limits["loss_rel_gap"]))
    g, g_at = worst_leaf_gap(prog["grad_norms"], ref["grad_norms"])
    d, d_at = worst_leaf_gap(prog["delta_norms"], ref["delta_norms"])
    rows.append({**row("first_grad_norm_worst_leaf_gap", g,
                       limits["grad_norm_gap"]), "leaf": g_at})
    rows.append({**row("param_change_norm_worst_leaf_gap", d,
                       limits["delta_norm_gap"]), "leaf": d_at})
    later = prog.get("window_losses", [])
    rows.append(row("window_losses_not_finite",
                    sum(not np.isfinite(v) for v in later), 0, exact=True))
    rows.append(row("window_loss_unchanged",
                    int(len(later) > 1 and len(set(later)) == 1), 0,
                    exact=True))
    rows.append(row("compiles_in_window", compiles_in_window, 0, exact=True))
    return rows
