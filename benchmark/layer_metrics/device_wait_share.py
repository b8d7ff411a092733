"""Share of the window that the gather thread waited for the device
(``pipeline.gather``'s ``device_us``, a ``block_until_ready``).  It
lies over the device's busy share by the time the input's transfer
takes on the device, which nothing else bounds; a gather thread that
always waits is a device-bound job."""

from benchmark import program_spans as ps


def read(obs):
    spans = ps.in_window(obs)
    if not spans or not ps.named(spans, "pipeline.gather") \
            or obs.window_s <= 0:
        return None
    wait_us = sum(s.get("device_us", 0.0)
                  for s in ps.named(spans, "pipeline.gather"))
    return 100.0 * wait_us / 1e6 / obs.window_s
