"""Ids a row reveals in one denoise pass:
``diffusion.revealed_ids / diffusion.denoise_passes``, the program's own
exact counts, both summed over the rows.  The static schedule gives
``block_length / denoise_steps`` (2.0 where blocks of 4 take 2 passes);
a reading off it says the schedule changed, not that the program got
faster.  ``None`` where the program counts no denoise pass."""


def read(obs):
    passes = obs.counters.get("diffusion.denoise_passes", 0.0)
    if passes <= 0:
        return None
    return obs.counters.get("diffusion.revealed_ids", 0.0) / passes
