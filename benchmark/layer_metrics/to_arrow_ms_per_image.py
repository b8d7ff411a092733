"""Milliseconds per image, inside ``readImages``, from decoded arrays to
the frame: image structs, ``pa.array`` and the record batch
(``io.to_arrow``) plus the table and its copy into ``numPartitions``
(``io.repartition``), over ``io.to_arrow``'s ``rows``."""

from benchmark import program_spans as ps


def read(obs):
    return ps.ms_per_row(obs, ("io.to_arrow", "io.repartition"), "io.to_arrow")
