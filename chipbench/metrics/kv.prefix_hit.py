"""Share of the prompt tokens admitted in the window that the cache
ledger served from shared blocks instead of prefilling them (percent):
the change of ``pc.prefix_tokens_hit`` over that plus the change of the
engine's ``prefill_tokens``."""


def read(run):
    hit = run.counters["prefix_tokens_hit"]
    total = hit + run.counters["prefill_tokens"]
    return 100.0 * hit / total if total else None
