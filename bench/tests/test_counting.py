"""The FLOP and byte counts behind mfu.* and the kernel rooflines."""
from pathlib import Path

import pytest

from bench import counting

PAPER_CNN = {"height": 28, "width": 28, "channels": 1, "c1": 16, "c2": 32,
             "hidden": 64, "n_classes": 10}


def test_cnn_forward_flops_by_hand():
    tiny = {"height": 4, "width": 4, "channels": 1, "c1": 2, "c2": 3,
            "hidden": 5, "n_classes": 2}
    # conv1 4*4*9*1*2, conv2 2*2*9*2*3, dense (1*1*3)*5 and 5*2; 2 a MAC
    assert counting.cnn_forward_flops(tiny) == 2 * (288 + 216 + 15 + 10)
    assert counting.cnn_forward_flops(PAPER_CNN) == 2 * (
        112896 + 903168 + 1568 * 64 + 640)


def test_client_round_flops_counts_whole_batches_only():
    f = counting.cnn_forward_flops(PAPER_CNN)
    assert counting.client_round_flops(PAPER_CNN, 330, 16, 10) == \
        3 * f * 320 * 10


def test_decision_bytes():
    # two bf16 planes written and read once, 26 bytes of state a user
    assert counting.decision_bytes(10, 3, 2) == 4 * 10 * 3 * 2 + 10 * 26


def test_kernel_calls_from_compiled_text():
    hlo = (Path(__file__).parent / "data" / "small_fedavg_hlo.txt").read_text()
    calls = counting.kernel_calls(hlo, ["_fedavg_kernel"])
    (info,) = calls.values()
    assert info["kernel"] == "_fedavg_kernel"
    # w [Np, 1] f32 and x [Np, Dp] f32 in, [1, Dp] f32 out
    (np_, dp) = (16, 384)
    assert info["bytes"] == 4 * (np_ + np_ * dp + dp)
    assert info["flops"] == 2 * np_ * dp


def test_shapes_and_bytes():
    s = counting.shapes("(f32[2,3]{1,0}, bf16[4]{0}, pred[5])")
    assert s == [("f32", (2, 3)), ("bf16", (4,)), ("pred", (5,))]
    assert counting.nbytes(s) == 24 + 8 + 5


def test_roofline_takes_the_larger_bound():
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert counting.roofline_seconds(1000.0, 20.0, peaks) == \
        pytest.approx(10.0)
    assert counting.roofline_seconds(10.0, 200.0, peaks) == \
        pytest.approx(20.0)
