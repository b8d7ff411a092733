"""Programs compiled or fetched from the persistent cache inside the
window (misses plus hits of ``compile_cache.stats()``): 0 when set-up
warmed every shape."""


def read(obs):
    c = obs.counters
    if "compile_cache.hits" not in c:
        return None
    return c["compile_cache.hits"] + c["compile_cache.misses"]
