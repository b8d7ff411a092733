"""Milliseconds of ``decode_f`` per file, inside ``readImages``: the
``io.decode`` spans over their ``rows`` (the decoder alone, without the
struct and Arrow build that ``to_arrow_ms_per_image`` reads)."""

from benchmark import program_spans as ps


def read(obs):
    return ps.ms_per_row(obs, ("io.decode",), "io.decode")
