"""The sparse grouped-query attention's share of its roofline: the least
time the chip could take for the query-key pairs the window SELECTED
(``vl_trunk_flops.attention_flops``: every query head's score and value
of ``head_dim``, ``4 H hd`` a pair, at the bf16 peak;
``attention_bytes``: the queries and outputs of every query head and the
keys and values of every key/value head once a layer, at the memory's
rate — whichever is larger) over the device seconds of the kernel in the
traced window.  The same work whatever implements it: a kernel that
computes whole tiles of keys reads low here, and that is the reading.
The pairs are the program's own count (``sparse.selected_pairs``),
scaled to the dispatched rows.  The ``pallas_call`` is called
``sparse_attention``.  ``None`` where its line is not among the ten
operations the reduction keeps, the program counts no pairs, or the
configuration has no ``sa_config``."""

from benchmark import sequence_flops as sf
from benchmark import vl_trunk_flops as vf
from benchmark.layer_metrics.indexer_roofline import dispatched, positions

KERNEL = "sparse_attention"


def read(obs):
    c = obs.config
    seconds = sf.kernel_seconds(obs, KERNEL)
    pairs = dispatched(obs, "sparse.selected_pairs")
    if seconds is None or pairs is None or "sa_config" not in c:
        return None
    least = sf.roofline_seconds(vf.attention_flops(c, pairs),
                                vf.attention_bytes(c, positions(obs)),
                                obs.peak)
    return 100.0 * least / obs.chips / seconds
