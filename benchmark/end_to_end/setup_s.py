"""Process start to the window's start: imports, weights and traffic
from the seed, compile or cache load, one warm job."""


def read(obs):
    return obs.setup_s
