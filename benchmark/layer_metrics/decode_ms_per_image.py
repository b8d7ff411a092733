"""Milliseconds of ``readImages`` per image it returned, over the
window: the decode layer, on the benchmark's clock.  Cells whose jobs
have no decode step report nothing."""


def read(obs):
    jobs = [j for j in obs.jobs if "decode" in j.spans]
    images = sum(j.images for j in jobs)
    if not images:
        return None
    return 1e3 * sum(j.spans["decode"] for j in jobs) / images
