"""Seconds the program spent tracing, lowering and compiling (or
loading) inside the window: Δ ``trace_s + lower_s + backend_s`` of
``compile_cache.stats()``, expected 0.0.  What ``compiles_in_window``
counts in events of the persistent cache, in seconds — and also a
retrace that never reaches that cache (a shape the in-memory jit cache
missed and the lowering then found already compiled)."""

KEYS = ("compile_cache.trace_s", "compile_cache.lower_s",
        "compile_cache.backend_s")


def read(obs):
    c = obs.counters
    if KEYS[0] not in c:        # a program from before PR 39
        return None
    return sum(c[k] for k in KEYS)
