"""Operations and bytes from shapes.

``flops_per_image`` of a configuration is counted here, from the layer
shapes of its plain reference (``reference/<config>.py``, written from
the published architecture): 2 x the multiply-accumulates of every
convolution at its output size.  Batch norm, ReLU, pooling and the bias
adds are left out — under 1% of the total, and the convention of the
papers' own counts.  The number is pinned in the configuration's file;
``tests/test_flops.py`` holds the file to this count.  It does not come
from the program or from XLA's cost analysis, which a later PR changes
when it changes the program.
"""

from __future__ import annotations

import importlib
from typing import List

from benchmark.reference import net as refnet


def conv_flops(kh: int, kw: int, cin: int, cout: int, out_h: int,
               out_w: int) -> int:
    """2 x multiply-accumulates of one convolution for one image."""
    return 2 * kh * kw * cin * cout * out_h * out_w


def reference_module(config_name: str):
    return importlib.import_module(f"benchmark.reference.{config_name}")


def conv_geometries(config_name: str) -> List[refnet.ConvGeometry]:
    ref = reference_module(config_name)
    return refnet.declare(ref.forward, (1,) + tuple(ref.INPUT_HW) + (3,)).convs


def flops_per_image(config_name: str) -> int:
    return sum(conv_flops(g.kh, g.kw, g.cin, g.cout, g.out_h, g.out_w)
               for g in conv_geometries(config_name))


if __name__ == "__main__":
    import sys

    for name in sys.argv[1:]:
        print(name, flops_per_image(name))
