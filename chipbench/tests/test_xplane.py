"""The reduction, on a hand-made trace whose answers are known, and on a
small trace recorded on the chip (data/*.json.gz, the IR of xplane.load)."""
import glob
import os

import pytest

from chipbench import xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def hand_made():
    # window 1000..2000 ns; ops: [1000,1200) [1100,1300) overlap; [1500,1600);
    # [1900,2100) runs past the window; [500,900) lies before it
    ops = [["fusion.1", "convolution fusion", 1000, 200], ["flash_fwd", "custom-call", 1100, 200],
           ["fusion.1", "convolution fusion", 1500, 100], ["all-reduce.3", "all-reduce", 1900, 200],
           ["fusion.9", "loop fusion", 500, 400]]
    spans = [["window", 1000, 1000], ["fetch", 1300, 150], ["step", 1450, 100], ["fetch", 1600, 250]]
    return {"devices": {"/device:TPU:0": ops}, "spans": spans}


def test_busy_union_and_idle_share():
    b = xplane.busy_seconds(hand_made())
    assert b["window_s"] == pytest.approx(1000e-9)
    assert b["busy_s"] == pytest.approx((300 + 100 + 100) * 1e-9)   # union, clipped


def test_idle_gaps_by_span():
    ir = hand_made()
    assert xplane.idle_gaps(ir) == [(1300, 1500), (1600, 1900)]
    g = xplane.gaps_by_span(ir)
    # 1300-1450 fetch, 1450-1500 step, 1600-1850 fetch, 1850-1900 nobody's
    assert g == pytest.approx({"fetch": 400e-9, "step": 50e-9, "window": 50e-9})


def test_kernel_time_by_name_and_collectives():
    ir = hand_made()
    assert xplane.seconds_by(ir, lambda n, c: "flash" in n) == pytest.approx(200e-9)
    assert xplane.seconds_by(ir, lambda n, c: "convolution" in c) == pytest.approx(300e-9)
    assert xplane.count_by(ir, xplane.is_collective) == 1
    assert xplane.exposed_seconds(ir, xplane.is_collective) == pytest.approx(100e-9)
    top = xplane.top_ops(ir, 2)
    assert top[0][0] == "convolution fusion:fusion.1" and top[0][1] == pytest.approx(300e-9)


def test_ir_round_trip(tmp_path):
    p = str(tmp_path / "ir.json.gz")
    xplane.save_ir(hand_made(), p)
    assert xplane.load_ir(p) == hand_made()


@pytest.mark.parametrize("path", sorted(glob.glob(os.path.join(DATA, "*_ir.json.gz"))))
def test_recorded_trace(path):
    ir = xplane.load_ir(path)
    b = xplane.busy_seconds(ir)
    assert 0 < b["busy_s"] <= b["window_s"]
    gaps = xplane.gaps_by_span(ir)
    idle = b["window_s"] - b["busy_s"]
    assert sum(gaps.values()) == pytest.approx(idle, rel=1e-6)
    assert xplane.top_ops(ir, 10)


def test_recorded_ernie_trace_reads_what_was_seen_by_hand():
    """Six steps of ernie3-base-mlm.s512-1chip recorded on a TPU v5 lite
    (PR 24): by hand, 35.7 ms of matmul fusions and 11.3 ms of flash
    kernels a step, the device busy throughout."""
    from chipbench.layer_metrics import _common as c

    ir = xplane.load_ir(os.path.join(DATA, "ernie_s512_ir.json.gz"))
    assert c.steps_in_trace(ir) == 6
    b = xplane.busy_seconds(ir)
    assert b["busy_s"] / b["window_s"] > 0.999
    assert xplane.seconds_by(ir, c.is_matmul) / 6 * 1e3 == pytest.approx(35.7, abs=0.2)
    flash = c.pallas_with_operand("bf16[192,512,64]")
    assert xplane.seconds_by(ir, flash) / 6 * 1e3 == pytest.approx(11.3, abs=0.2)
    assert xplane.count_by(ir, flash) in range(6 * 36 - 36, 6 * 36 + 37)   # 12 layers x 3 kernels a step
    assert xplane.count_by(ir, xplane.is_collective) == 0


def test_short_name():
    text = ('%jvp_jit__flash_fwd_jit__.36 = (bf16[192,512,64]{2,1,0:T(8,128)(2,1)}, f32[192,512,1]{2,1,0}) '
            'custom-call(s32[1]{0:T(128)} %constant.63, bf16[192,512,64]{2,1,0} %bitcast.2309), '
            'custom_call_target="tpu_custom_call", operand_layout_constraints={s32[1]{0}}')
    assert xplane.short_name(text) == (
        "jvp_jit__flash_fwd_jit__.36", "custom-call:tpu_custom_call:out2:s32[1],bf16[192,512,64]")
    assert xplane.short_name("%fusion.7 = f32[8]{0} fusion(f32[8]{0} %p), kind=kOutput, calls=%c") == (
        "fusion.7", "fusion:kOutput")
    assert xplane.short_name("%all-reduce.3 = f32[8]{0} all-reduce(f32[8]{0} %p)")[1] == "all-reduce"
