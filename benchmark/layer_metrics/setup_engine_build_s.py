"""Seconds of set-up the engine took to build itself: Σ self time of
``engine.build`` before the window (the cast to the compute dtype, the
sharding policy, the weights' placement, the jit lookup; a compile it
caused is a span under it and counted there, not here)."""

from benchmark import setup_spans


def read(obs):
    return setup_spans.self_s(obs, "engine.build")
