"""How much of the step the routed experts' products are: device seconds
of the expert kernel (``%grouped_matmul.<n>`` among the trace's
operations, as ``grouped_matmul_roofline`` knows it) over the summed
device time of the program's executions in the traced window, percent.
``None`` where the kernel is not among the ten operations kept."""

from benchmark import sequence_flops as sf
from benchmark.layer_metrics.grouped_matmul_roofline import KERNEL


def read(obs):
    seconds = sf.kernel_seconds(obs, KERNEL)
    if seconds is None or obs.trace.module_s <= 0:
        return None
    return 100.0 * seconds / obs.trace.module_s
