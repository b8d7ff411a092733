"""Median seconds of one job (one ``transform`` call, with its
``readImages`` where the cell has one), on the benchmark's clock."""

import statistics


def read(obs):
    if not obs.jobs:
        return None
    return statistics.median(j.end - j.start for j in obs.jobs)
