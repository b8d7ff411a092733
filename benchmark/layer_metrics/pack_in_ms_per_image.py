"""Milliseconds per row of ``transform``'s input packing: image structs
to one resized uint8 batch (``arrowStructsToBatch``), the
``transform.pack_in`` spans over their ``rows``."""

from benchmark import program_spans as ps


def read(obs):
    return ps.ms_per_row(obs, ("transform.pack_in",), "transform.pack_in")
