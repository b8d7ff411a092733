"""Seconds of set-up in which the program's own Python was traced and
lowered: Σ ``compile.trace`` + ``compile.lower`` before the window under
a span of the program's.  Paid on a warm compile cache too (the cache's
key is made from the lowered module)."""

from benchmark import setup_spans


def read(obs):
    return setup_spans.parented_s(obs, ("compile.trace", "compile.lower"))
