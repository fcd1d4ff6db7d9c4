"""Mean live rows per decoded step over the window: tokens generated over
(chunks run x tokens per chunk), from the engine's counters."""


def read(run):
    steps = run.delta("chunks") * run.st1["decode_chunk"]
    return run.delta("tokens_generated") / steps if steps else None
