"""Milliseconds per dispatch that the gather thread works on the host
once the device is done: D2H copy, cast and trim — ``pipeline.gather``'s
duration less its ``device_us`` (the ``block_until_ready`` wait)."""

from benchmark import program_spans as ps


def read(obs):
    spans = ps.in_window(obs)
    gathers = spans and ps.named(spans, "pipeline.gather")
    if not gathers:
        return None
    host_us = sum(s["dur_us"] - s.get("device_us", 0.0) for s in gathers)
    return 1e-3 * host_us / len(gathers)
