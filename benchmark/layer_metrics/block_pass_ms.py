"""Milliseconds of device time one pass of the generation loop takes: a
block's positions of every dispatched row through the layers and then
the head and the choice (a denoise pass) or the write into the cache
(the commit pass).  The loop's device seconds in the traced window
(``diffusion_flops.loop_seconds``: the ``%while`` whose carry leads with
the generated ids) over the passes the window's dispatches ran
(``diffusion.denoise_passes`` + ``diffusion.commit_passes`` a row, the
program's own count, times the program's executions in the trace).
``None`` where the loop's line is not among the ten operations kept or
the program counts no pass."""

from benchmark import diffusion_flops as df


def read(obs):
    seconds, passes = df.loop_seconds(obs), df.passes_per_dispatch(obs)
    if seconds is None or passes is None:
        return None
    return 1e3 * seconds / (obs.trace.module_executions
                            * (passes["denoise"] + passes["commit"]))
