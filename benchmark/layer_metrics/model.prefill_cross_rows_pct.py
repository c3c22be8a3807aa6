"""Rows the cross-decoder processed as a percentage of the real tokens
through the self-decoder, over the window's PREFILL programs (their own
``prefill_cross_rows`` and ``prefill_self_rows``, from the engine's
program records): a prefill runs everything behind the one shared
cache's keys and values on a prompt's last row alone, so this reads one
over the mean prompt length (about 0.02 at 5k tokens); 100 would mean
that the shortcut is gone. ``None`` for an engine whose records carry
no such counters.

layer: model step (the serving models' paged prefill forwards) —
source: program_counter — moves: serve_request_p95_ms.
"""
from benchmark.trace import diff_events


def reduce(trace, facts):
    return diff_events.cross_rows_percent(facts)
