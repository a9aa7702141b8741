"""Operation and byte counts against hand counts at small shapes."""
import jax
import numpy as np

from bench import kinds
from bench.counts import quorum_aggregate as QA
from bench.counts import student_forward as SF

CNN = kinds.load("cnn")

WRN = {"kind": "wrn", "depth": 10, "widen": 1}
MBV2 = {"kind": "mbv2", "stem": 8, "blocks": [[1, 8, 1, 1], [2, 16, 1, 2]]}


def test_wrn_flops_by_hand():
    # conv0 3->16 on 8x8; g0b0 16->16 (two 3x3); g1b0 16->32 stride 2 to
    # 4x4 (two 3x3 and a 1x1 shortcut); g2b0 32->16 stride 2 to 2x2
    want = (2 * 64 * 9 * 3 * 16
            + 2 * (2 * 64 * 9 * 16 * 16)
            + 2 * 16 * 9 * 16 * 32 + 2 * 16 * 9 * 32 * 32 + 2 * 16 * 16 * 32
            + 2 * 4 * 9 * 32 * 16 + 2 * 4 * 9 * 16 * 16 + 2 * 4 * 32 * 16)
    assert want == 1_163_264
    assert SF.flops(WRN, 16, (8, 8, 3), 1) == want
    assert SF.flops(WRN, 16, (8, 8, 3), 5) == 5 * want


def test_mbv2_flops_by_hand():
    want = (2 * 16 * 9 * 3 * 8                          # stem
            + 2 * 16 * 9 * 8 + 2 * 16 * 8 * 8           # dw, project
            + 2 * 16 * 8 * 16 + 2 * 4 * 9 * 16          # expand, dw /2
            + 2 * 4 * 16 * 16                           # project
            + 2 * 4 * 16 * 12)                          # last 1x1
    assert want == 20_096
    assert SF.flops(MBV2, 12, (4, 4, 3), 1) == want


def _conv_weights(p):
    leaves = jax.tree_util.tree_leaves_with_path(p)
    return sum(int(np.prod(x.shape)) for path, x in leaves
               if jax.tree_util.keystr(path).endswith("['kernel']")
               and "fc" not in jax.tree_util.keystr(path))


def test_bytes_count_the_reference_weights():
    for spec, width, shape in [(WRN, 16, (8, 8, 3)), (MBV2, 12, (4, 4, 3))]:
        p = CNN.init_student(jax.random.key(0), spec, width, 10)
        h, w, c = shape
        assert SF.bytes_moved(spec, width, shape, 3) == 4 * (
            _conv_weights(p) + 3 * h * w * c + 3 * width)


def test_quorum_aggregate_by_hand():
    assert QA.flops(3, 4, 5, 6) == 2 * 3 * 4 * 5 * 6
    assert QA.bytes_moved(3, 4, 5, 6) == 4 * (3 * (4 * 5 + 5 * 6) + 6 + 24)
