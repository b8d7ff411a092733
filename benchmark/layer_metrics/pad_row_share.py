"""Share of the rows the engine dispatched that were padding:
``engine.pad_rows / (engine.rows + engine.pad_rows)``, exact counts."""


def read(obs):
    rows = obs.counters.get("engine.rows", 0.0)
    pad = obs.counters.get("engine.pad_rows", 0.0)
    if rows + pad <= 0:
        return None
    return 100.0 * pad / (rows + pad)
