"""Share of ``transform`` that no span names yet: the self time of
``transform.run`` (slicing the frame, building the engine, joining the
outputs) plus that of the ``pipeline.run`` bracket under it (no stage
thread has a span open: thread start and join, queue polls), over
``transform.run``'s duration."""

from benchmark import program_spans as ps


def read(obs):
    spans = ps.in_window(obs)
    whole = spans and ps.total_s(spans, "transform.run")
    if not whole:
        return None
    return 100.0 * (ps.self_s(spans, "transform.run")
                    + ps.self_s(spans, "pipeline.run")) / whole
