"""Milliseconds per row of ``transform``'s output packing: the model's
rows to an Arrow list column and ``withColumn`` (the predictor's decode
and top-k with it), the ``transform.pack_out`` spans over their
``rows``."""

from benchmark import program_spans as ps


def read(obs):
    return ps.ms_per_row(obs, ("transform.pack_out",), "transform.pack_out")
