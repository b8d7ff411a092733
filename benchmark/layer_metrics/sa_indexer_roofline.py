"""The lightning indexer's share of its roofline at ``sa_config``'s
heads: the least time the chip could take for the causal pairs the
window scored (``vl_trunk_flops.indexer_flops``: ``indexer_num_heads``
products of ``indexer_head_dim`` and the weighted sum a pair, at the bf16
peak; ``indexer_bytes``: the indexer's queries, its key and its head
weights once, at the memory's rate — whichever is larger) over the
device seconds of the kernel in the traced window.  The pairs are the
program's own count (``sparse.indexed_pairs``), scaled to the dispatched
rows.  The ``pallas_call`` is called ``indexer_scores``.  ``None`` where
its line is not among the ten operations the reduction keeps, the
program counts no pairs, or the configuration has no ``sa_config``
(``indexer_roofline`` reads the same kernel at DeepSeek's
``index_n_heads``)."""

from benchmark import sequence_flops as sf
from benchmark import vl_trunk_flops as vf
from benchmark.layer_metrics.indexer_roofline import dispatched, positions

KERNEL = "indexer_scores"


def read(obs):
    c = obs.config
    seconds = sf.kernel_seconds(obs, KERNEL)
    pairs = dispatched(obs, "sparse.indexed_pairs")
    if seconds is None or pairs is None or "sa_config" not in c:
        return None
    least = sf.roofline_seconds(vf.indexer_flops(c, pairs),
                                vf.indexer_bytes(c, positions(obs)), obs.peak)
    return 100.0 * least / obs.chips / seconds
