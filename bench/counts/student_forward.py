"""Operations and bytes of one student's portion forward, from shapes.

Operations are 2 per multiply-add of every convolution (depthwise ones
included); batch norm, activations and pooling are not counted. Bytes are
the least a forward must move: its weights (float32, as served) once, its
input images and its output features. Nothing here reads the compiled
program, so a change of implementation cannot change the count.
"""
from __future__ import annotations

from typing import Dict, List, Tuple


def _same(n: int, stride: int) -> int:
    return -(-n // stride)


def layers(spec: Dict, width: int, image_shape: Tuple[int, int, int]
           ) -> List[Tuple[int, int, int, int, int, int]]:
    """(out_h, out_w, k, cin_per_group, cout, n_params) of every conv."""
    h, w, c = image_shape
    out = []

    def conv(k, cin, cout, stride=1, groups=1):
        nonlocal h, w
        h, w = _same(h, stride), _same(w, stride)
        out.append((h, w, k, cin // groups, cout, k * k * (cin // groups)
                    * cout))

    if spec["kind"] == "wrn":
        n = (spec["depth"] - 4) // 6
        widths = [16 * spec["widen"], 32 * spec["widen"], width]
        conv(3, c, 16)
        cin = 16
        for g in range(3):
            for b in range(n):
                cout, stride = widths[g], (2 if g and not b else 1)
                conv(3, cin, cout, stride)            # conv1
                conv(3, cout, cout)                   # conv2
                if cin != cout:
                    conv(1, cin, cout)                # shortcut, same output
                cin = cout
        return out
    if spec["kind"] == "mbv2":
        conv(3, c, spec["stem"])
        cin = spec["stem"]
        for exp, cout, reps, stride in spec["blocks"]:
            for i in range(reps):
                mid = cin * exp
                if exp != 1:
                    conv(1, cin, mid)
                conv(3, mid, mid, stride if i == 0 else 1, groups=mid)
                conv(1, mid, cout)
                cin = cout
        conv(1, cin, width)
        return out
    raise KeyError(spec["kind"])


def flops(spec: Dict, width: int, image_shape, rows: int) -> float:
    """Operations of the forward over ``rows`` images."""
    return float(rows * sum(2 * oh * ow * k * k * cg * co
                            for oh, ow, k, cg, co, _ in layers(
                                spec, width, image_shape)))


def bytes_moved(spec: Dict, width: int, image_shape, rows: int) -> float:
    """Least bytes the forward over ``rows`` images moves: float32 conv
    weights once, the input images and the output features."""
    h, w, c = image_shape
    weights = sum(n for *_, n in layers(spec, width, image_shape))
    return float(4 * (weights + rows * h * w * c + rows * width))
