"""Real (non-garbage) rows a prefill dispatch carried, on average over
the traced part's dispatches (runtime counters ``prefill_live_rows`` /
``prefill_chunks``, differenced like ``prefix_hit_pct``).  A dispatch has
``prefill_rows`` rows; the rest are garbage rows of groups smaller than
the dispatch.  None from a program that does not count live rows."""


def read(run):
    if "prefill_live_rows" not in run.log.stats_open:
        return None
    chunks = run.counter("prefill_chunks")
    if chunks <= 0:
        return None
    return run.counter("prefill_live_rows") / chunks
