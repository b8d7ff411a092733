"""Share of the window in which the pipeline's dispatch thread — the
one that feeds the device — waited for a prepared batch
(``pipeline.dispatch_in_stall_s``, a host event on the host clock)."""


def read(obs):
    stall = obs.counters.get("pipeline.dispatch_in_stall_s")
    if stall is None or obs.window_s <= 0:
        return None
    return 100.0 * stall / obs.window_s
