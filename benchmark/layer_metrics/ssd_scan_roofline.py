"""The state-space scan kernel's share of its roofline: the least time
the chip could take for the recurrence of every dispatched row in every
block (``sequence_flops.scan_flops`` at the bf16 peak, ``scan_bytes`` at
the memory's rate, whichever is larger; the count does not depend on
what implements the scan) over the device seconds of the kernel in the
traced window.  The kernel is known by its name: the ``pallas_call`` is
called ``ssd_scan`` and the trace's line reads ``%ssd_scan.<n> <shape>
custom-call``.  ``None`` where that line is not among the ten
operations the reduction keeps, or the configuration has no mixer."""

from benchmark import sequence_flops as sf

KERNEL = "ssd_scan"


def read(obs):
    c = obs.config
    if "mamba_n_heads" not in c:
        return None
    t = c["sequence_length"]
    return sf.kernel_roofline_share(
        obs, KERNEL,
        sf.scan_flops(c["mamba_n_heads"], c["mamba_d_head"],
                      c["mamba_d_state"], t),
        sf.scan_bytes(c["mamba_n_heads"], c["mamba_d_head"],
                      c["mamba_n_groups"], c["mamba_d_state"], t, itemsize=2))
