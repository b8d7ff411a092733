"""Seconds of set-up between the lowered module and its executable:
Σ ``compile.backend`` before the window under a span of the program's —
the persistent cache's retrieval where it hit, XLA's compile where it
missed (the span's ``cache`` says which)."""

from benchmark import setup_spans


def read(obs):
    return setup_spans.parented_s(obs, ("compile.backend",))
