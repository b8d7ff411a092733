"""Milliseconds of ``jax.device_put`` per dispatch, on the host: staging
and enqueue (``engine.h2d``).  Not the transfer's time on the device:
nothing there waits for it (``device_wait_share`` bounds that)."""

from benchmark import program_spans as ps


def read(obs):
    spans = ps.in_window(obs)
    count = spans and len(ps.named(spans, "engine.h2d"))
    if not count:
        return None
    return 1e3 * ps.total_s(spans, "engine.h2d") / count
