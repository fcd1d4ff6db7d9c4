"""Share of the prompt tokens admitted in the window that the prefix cache
served (partial and full hits), from the engine's counters."""


def read(run):
    admitted = (run.delta("prefill_tokens_computed")
                + run.delta("prefill_tokens_saved"))
    return run.delta("cached_tokens_served") / admitted if admitted else None
