"""The reference's own way from file bytes to the model's input: decode
with PIL, resize with PIL's bilinear filter, RGB order.  (PIL is the
installation's JPEG library, not the program's code; the program may
decode with whatever it likes and is held to this result.)"""

from __future__ import annotations

import io
from typing import Sequence

import numpy as np


def decode_resize_rgb(blobs: Sequence[bytes], height: int, width: int
                      ) -> np.ndarray:
    from PIL import Image

    out = np.empty((len(blobs), height, width, 3), np.uint8)
    for i, blob in enumerate(blobs):
        img = Image.open(io.BytesIO(blob)).convert("RGB")
        if img.size != (width, height):
            img = img.resize((width, height), Image.BILINEAR)
        out[i] = np.asarray(img, np.uint8)
    return out
