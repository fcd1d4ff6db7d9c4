"""Share of the admission prefills' token slots that computed nothing new:
1 - sum(prompt_tokens - cached_tokens) / sum(batch x bucket) over the
program's ``serve.admit`` spans in the traced window.  Padding rows,
padding columns and the cached prefixes a partial hit recomputes all count
as waste, as useful FLOPs leave them out (``benchlib/flops.py``)."""
_KEYS = ("prompt_tokens", "cached_tokens", "batch", "bucket")


def read(run):
    t = run.trace
    if t is None:
        return None
    useful = slots = 0
    for a in t.annotations:
        if a.name != "serve.admit" or not all(k in a.args for k in _KEYS):
            continue
        n = {k: int(a.args[k]) for k in _KEYS}
        useful += n["prompt_tokens"] - n["cached_tokens"]
        slots += n["batch"] * n["bucket"]
    return 1.0 - useful / slots if slots else None
