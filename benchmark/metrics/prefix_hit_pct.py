"""Cache manager: share of prompt tokens served from a cached prefix."""


def value(run, trace):
    total = run.get("prompt_tokens", 0)
    return 100.0 * run["prefix_tokens"] / total if total else None
