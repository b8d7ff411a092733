"""Token-expert pairs computed here for each token that was routed:
``moe.pairs / moe.tokens``, the program's own exact counts (``moe.tokens``
is tokens x expert layers).  Uniform routing gives ``experts a token x
held / routed`` (0.5 where 32 of 256 are held and a token takes 4); a
reading off it says the routing changed, not that the program got
faster.  ``None`` where the program counts no routed tokens."""


def read(obs):
    tokens = obs.counters.get("moe.tokens", 0.0)
    if tokens <= 0 or "moe.pairs" not in obs.counters:
        return None
    return obs.counters["moe.pairs"] / tokens
