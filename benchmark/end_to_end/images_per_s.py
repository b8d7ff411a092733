"""All images of all jobs that finished, over the time from the window's
start to the end of the last job (the job in flight when ``--seconds``
is up is finished and counted).  Host clock, over the whole window."""


def read(obs):
    if not obs.jobs or obs.window_s <= 0:
        return None
    return sum(j.images for j in obs.jobs) / obs.window_s
