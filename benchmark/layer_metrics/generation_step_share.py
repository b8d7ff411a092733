"""How much of the step the generation loop is: its device seconds
(``diffusion_flops.loop_seconds``) over the summed device time of the
program's executions in the traced window, percent; the rest is the
prefill.  ``None`` where the loop's line is not among the ten operations
kept."""

from benchmark import diffusion_flops as df


def read(obs):
    seconds = df.loop_seconds(obs)
    if seconds is None or obs.trace.module_s <= 0:
        return None
    return 100.0 * seconds / obs.trace.module_s
