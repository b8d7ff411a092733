"""The whole step's share of the chip's peak: real (non-pad) images
dispatched in the traced window x the configuration's pinned
``flops_per_image``, over the summed device time of the program's
executions in the trace x the chip's bf16 peak.  It is taken over step
time, not over the window: idle time between steps is
``device_idle_share``'s."""


def read(obs):
    if obs.trace is None or obs.trace.module_s <= 0:
        return None
    images = obs.counters.get("engine.rows", 0.0)
    if images <= 0:
        return None
    flops = images * float(obs.config["flops_per_image"]) / obs.chips
    return 100.0 * flops / (obs.trace.module_s
                            * float(obs.peak["bf16_flops_per_s"]))
