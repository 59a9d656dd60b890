"""The trace reduction, on a synthetic trace with known answers and on a
small trace recorded on a TPU v5e."""
from pathlib import Path

import pytest

from bench import counting, trace

DATA = Path(__file__).parent / "data"


@pytest.fixture(scope="module")
def synthetic(tmp_path_factory):
    from jax.profiler import ProfileData
    path = tmp_path_factory.mktemp("xplane") / "synthetic.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(
        (DATA / "synthetic.pbtxt").read_text()))
    return str(path)


def test_busy_is_the_union_of_leaf_op_intervals(synthetic):
    s = trace.reduce(synthetic, window_s=11e-6)
    # leaf ops at [1000, 3000), [2000, 4000) and [11000, 12000) ns: the
    # overlap counts once; the while op around them and the module span on
    # its own line do not make the gap between them busy
    assert s.busy_s == pytest.approx(4000e-9)
    assert s.idle_share == pytest.approx(1 - 4000 / 11000)


def test_ops_are_named_by_instruction_with_self_time(synthetic):
    s = trace.reduce(synthetic, window_s=1.0)
    assert trace.instruction("%fusion.3 = f32[2] fusion(f32[2] %x)") == \
        "fusion.3"
    # the while spans 12000 ns, of which its body ops take 5000
    assert s.op_seconds["while.7"] == pytest.approx(7000e-9)
    assert s.op_seconds["fusion.1"] == pytest.approx(3000e-9)


def test_kernel_time_by_instruction_name(synthetic):
    calls = {"_fedavg_reduce.1": {"kernel": "_fedavg_kernel", "bytes": 1,
                                  "flops": 1}}
    s = trace.reduce(synthetic, ["_fedavg_kernel"], calls, window_s=1.0)
    assert s.kernel_seconds == {"_fedavg_kernel": pytest.approx(2000e-9)}
    assert s.call_counts == {"_fedavg_reduce.1": 1}
    assert s.call_seconds["_fedavg_reduce.1"] == pytest.approx(2000e-9)


def test_idle_gaps_are_named_by_the_host(synthetic):
    s = trace.reduce(synthetic, window_s=1.0)
    name, secs = s.gaps[0]
    assert name == "host_work"                 # not the enclosing "main"
    assert secs == pytest.approx(7000e-9)
    b = s.breakdown()
    assert b["device_ops"][0] == ["while.7", pytest.approx(7000e-9)]
    assert len(b["idle_gaps"]) <= 10


def test_recorded_tpu_trace():
    """Three calls of a small jitted matmul and three of the Pallas FedAvg
    kernel, recorded on one TPU v5e."""
    hlo = (DATA / "small_fedavg_hlo.txt").read_text()
    calls = counting.kernel_calls(hlo, ["_fedavg_kernel"])
    assert len(calls) == 1
    s = trace.reduce(str(DATA / "small.xplane.pb"), ["_fedavg_kernel"],
                     calls)
    assert 0 < s.busy_s < s.window_s
    (inst,) = calls
    assert s.call_counts[inst] == 3
    assert 0 < s.kernel_seconds["_fedavg_kernel"] <= s.busy_s
