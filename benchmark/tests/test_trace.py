"""The trace reduction on a small recorded trace (data/small_trace.pbtxt:
the names of a real v5e trace, times shortened)."""

import os

import pytest

DATA = os.path.join(os.path.dirname(__file__), "data")


@pytest.fixture(scope="module")
def reduced(tmp_path_factory):
    from jax.profiler import ProfileData

    from benchmark import trace
    with open(os.path.join(DATA, "small_trace.pbtxt")) as f:
        raw = ProfileData.text_proto_to_serialized_xspace(f.read())
    d = tmp_path_factory.mktemp("trace") / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "vm.xplane.pb").write_bytes(raw)
    return trace.reduce(str(tmp_path_factory.getbasetemp()))


def test_only_the_interval_between_the_marks_is_reduced(reduced):
    assert reduced["chips"] == 1
    # the marks: 0.5 ms, and 15 ms + 4 us
    assert reduced["window_s"] == pytest.approx(14.504e-3)
    # 2 x (0.5 + 1.0) ms of framer ops; the de-framer's 0.8 ms at 21 ms
    # lie past the second mark
    assert reduced["busy_s"] == pytest.approx(3.0e-3)
    assert {m["name"] for m in reduced["modules"]} == {"jit_fused32"}


def test_a_programs_device_time_is_its_ops_not_its_span(reduced):
    mods = {m["name"]: m for m in reduced["modules"]}
    assert mods["jit_fused32"]["count"] == 2
    assert mods["jit_fused32"]["device_s"] == pytest.approx(3.0e-3)
    assert mods["jit_fused32"]["span_s"] == pytest.approx(4.0e-3)


def test_ops_are_named_by_result_and_shape(reduced):
    ops = dict(reduced["device_ops"])
    assert ops["%fused32.3 = u32[1,8,8,128]"] == pytest.approx(2.0e-3)
    assert ops["%_pallas_apply32.1 = u32[32,4,32768]"] == \
        pytest.approx(1.0e-3)
    assert all(len(name) <= 80 for name in ops)


def test_idle_time_is_told_by_the_host_spans_open_during_it(reduced):
    gaps = dict(reduced["idle_gaps"])
    # PjitFunction is open over [0, 8] and [10, 14] ms, 11.5 ms of it
    # after the first mark; the device is busy for 1.5 ms inside each
    assert gaps["PjitFunction(fused32)"] == pytest.approx(8.5e-3)
    assert gaps["XlaDelinearize"] == pytest.approx(4.0e-3)
    bare = [v for k, v in gaps.items() if k.startswith("no host span")]
    # [8, 10] and [14, 15.004] ms have no host span
    assert bare == [pytest.approx(3.004e-3)]
    assert not any(k.startswith("benchmark.") for k in gaps)
    assert len(reduced["idle_gaps"]) <= 10 and len(reduced["device_ops"]) <= 10


def test_a_trace_without_its_marks_is_an_error(tmp_path):
    from jax.profiler import ProfileData

    from benchmark import trace
    with open(os.path.join(DATA, "small_trace.pbtxt")) as f:
        text = f.read().replace("benchmark.interval_b", "something.else")
    (tmp_path / "vm.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(text))
    with pytest.raises(ValueError, match="interval_a before"):
        trace.reduce(str(tmp_path))
