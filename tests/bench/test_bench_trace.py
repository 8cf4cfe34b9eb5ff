"""bench/trace.py on a small synthetic trace (times in ns)."""

import pytest

import benchcells  # noqa: F401  (puts the checkout's root on sys.path)
from bench import trace as T


def _planes(devices):
    host = {"name": "/host:CPU", "lines": [{"name": "python", "events": [
        ("bench.window", 1000, 9000),        # window: 1000..10000
        ("bench.step", 1000, 4000),          # 1000..5000
        ("bench.next_batch", 5000, 1000),    # 5000..6000
        ("bench.step", 6000, 4000),          # 6000..10000
        ("bench.cache_clear", 6100, 400),    # 6100..6500, inside a step
        ("not.ours", 0, 20000),
    ]}]}
    planes = [host]
    for i, ops in enumerate(devices):
        planes.append({"name": f"/device:TPU:{i}", "lines": [
            {"name": "XLA Modules", "events": [("jit_step", 0, 20000)]},
            {"name": "XLA Ops", "events": ops}]})
    return planes


# device 0: busy 1500..3000 (two overlapping ops), 3500..4500, 6600..9000;
# an op before the window that must not count
DEV0 = [("quant_matmul", 1500, 1000), ("fusion.1", 2000, 1000),
        ("quant_matmul", 3500, 1000), ("lmhead_ce_fwd", 6600, 2400),
        ("fusion.9", 100, 500)]


def test_busy_union_idle_share_and_kernel_time():
    red = T.reduce(_planes([DEV0]))
    assert red["window_s"] == pytest.approx(9e-6)
    busy = (3000 - 1500) + (4500 - 3500) + (9000 - 6600)
    assert red["busy_s"] == pytest.approx(busy / 1e9)
    assert red["op_time_s"]["quant_matmul"] == pytest.approx(2000 / 1e9)
    assert "fusion.9" not in red["op_time_s"]
    assert T.kernel_time(red, r"^quant_matmul$") == (pytest.approx(2e-6), 2)


def test_gaps_attributed_to_innermost_host_span():
    red = T.reduce(_planes([DEV0]))
    idle = red["idle_by_span_s"]
    # gaps: 1000..1500, 3000..3500, 4500..6600 (mid 5550: next_batch),
    # 9000..10000 (mid 9500: step)
    assert idle["bench.step"] == pytest.approx((500 + 500 + 1000) / 1e9)
    assert idle["bench.next_batch"] == pytest.approx(2100 / 1e9)
    assert red["longest_gaps"][0] == ("bench.next_batch", pytest.approx(2.1e-6))
    gap_at_clear = T.reduce(_planes([[("x", 1000, 5100), ("y", 6500, 3500)]]))
    assert gap_at_clear["idle_by_span_s"] == {"bench.cache_clear": pytest.approx(4e-7)}


def test_busy_is_averaged_over_chips():
    red = T.reduce(_planes([DEV0, [("quant_matmul", 1000, 9000)]]))
    assert red["n_devices"] == 2
    one = (1500 + 1000 + 2400) / 1e9
    assert red["busy_s"] == pytest.approx((one + 9e-6) / 2)
    assert red["op_time_s"]["quant_matmul"] == pytest.approx((2000 + 9000) / 2e9)


def test_breakdown_lists_top_ops_and_gaps():
    bd = T.breakdown(T.reduce(_planes([DEV0])))
    assert bd["device_ops"][0] == ["lmhead_ce_fwd", pytest.approx(2.4e-6)]
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    assert bd["idle_gaps"][0][0] == "bench.next_batch"


@pytest.mark.parametrize("drop", ["window", "device"])
def test_trace_without_window_or_device_is_refused(drop):
    planes = _planes([DEV0])
    if drop == "window":
        planes[0]["lines"][0]["events"] = [e for e in planes[0]["lines"][0]["events"]
                                           if e[0] != "bench.window"]
    else:
        planes = planes[:1]
    with pytest.raises(ValueError):
        T.reduce(planes)


def test_kernel_found_by_its_name_or_operands():
    qmm = '%quant_matmul.54 = f32[4096,2048]{1,0} custom-call(f32[4096,2048]{1,0} %x)'
    use = '%fusion.3 = f32[4096,2048]{1,0} fusion(f32[4096,2048]{1,0} %quant_matmul.54)'
    head = '%jvp__.3 = (f32[4096,1]{1,0}) custom-call(f32[2048,92672]{1,0} %pad.0)'
    red = T.reduce(_planes([[(qmm, 2000, 1000), (use, 3000, 500), (head, 4000, 700)]]))
    # a consumer that names the kernel's output as an operand is not the kernel
    assert T.kernel_time(red, r"^quant_matmul") == (pytest.approx(1e-6), 1)
    assert T.kernel_time(red, lambda op: "f32[2048,92672]" in op) == (pytest.approx(7e-7), 1)
    assert T.kernel_time(red, r"lmhead") == (0, 0)
    assert T.breakdown(red)["device_ops"][0] == ["quant_matmul.54", pytest.approx(1e-6)]
