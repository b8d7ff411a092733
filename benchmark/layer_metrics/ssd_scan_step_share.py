"""How much of the step the distinctive part is: device seconds of the
state-space scan kernel (``%ssd_scan.<n>`` among the trace's
operations, as ``ssd_scan_roofline`` knows it) over the summed device
time of the program's executions in the traced window, percent.
``None`` where the kernel is not among the ten operations kept."""

from benchmark import sequence_flops as sf
from benchmark.layer_metrics.ssd_scan_roofline import KERNEL


def read(obs):
    seconds = sf.kernel_seconds(obs, KERNEL)
    if seconds is None or obs.trace.module_s <= 0:
        return None
    return 100.0 * seconds / obs.trace.module_s
