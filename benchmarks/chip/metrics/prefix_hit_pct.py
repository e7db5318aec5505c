"""Share of admitted prompt tokens whose K/V the prefix cache mapped
(runtime counters ``shared_tokens`` / ``prompt_tokens``, differenced at the
edges of the window's traced part)."""


def read(run):
    prompt = run.counter("prompt_tokens")
    if prompt <= 0:
        return None
    return 100.0 * run.counter("shared_tokens") / prompt
