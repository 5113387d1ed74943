"""The reduction from a profiler trace to busy time, idle gaps and kernel
time, and the operations and bytes computed from shapes, against numbers
worked out by hand. CPU only."""
import json
import os

import pytest

from harness import flops, trace
from harness.trace import Op, Span

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs")


def synthetic():
    # Host: fetch 0-10, dispatch 10-20, read-back 20-100 (ns).
    spans = [Span("get_batch_device", 0, 10), Span("step_dispatch", 10, 20),
             Span("loss_readback", 20, 100)]
    # Chip 0: ingest 12-15, step ops 18-40 and 35-60 (overlapping), 70-95.
    # Chip 1: one op 30-50. An op outside the window (150-160) is dropped.
    ops = [Op(0, "copy", "jit_reassemble_window", 12, 15),
           Op(0, "fusion", "jit_train_step", 18, 40),
           Op(0, "fusion", "jit_train_step", 35, 60),
           Op(0, "dot", "jit_train_step", 70, 95),
           Op(1, "dot", "jit_train_step", 30, 50),
           Op(0, "dot", "jit_train_step", 150, 160)]
    return ops, spans


def test_busy_is_the_union_of_op_intervals_averaged_over_chips():
    r = trace.reduce(*synthetic(), devices=2)
    assert r.window == (0, 100)
    # chip 0: 3 + (18..60 = 42) + 25 = 70; chip 1: 20.
    assert r.busy_ns == [70, 20]
    assert r.busy_s == pytest.approx(45e-9)
    assert r.window_s == pytest.approx(100e-9)


def test_kernel_time_by_name_and_by_program():
    r = trace.reduce(*synthetic(), devices=2)
    secs = r.op_seconds()
    assert secs["fusion"] == pytest.approx(47e-9)
    assert secs["dot"] == pytest.approx(45e-9)
    assert secs["copy"] == pytest.approx(3e-9)
    assert r.module_seconds(("reassemble",)) == pytest.approx(3e-9)
    # A program's time is the union of its ops over the whole trace:
    # chip 0 18..60, 70..95 and 150..160; chip 1 30..50.
    assert r.module_seconds(("train_step",)) == pytest.approx(97e-9)


def test_idle_gaps_go_to_the_host_span_that_overlaps_them_most():
    r = trace.reduce(*synthetic(), devices=2)
    # chip 0 idle: 0-12 (fetch 10, dispatch 2), 15-18 (dispatch 3 of 3),
    # 60-70 and 95-100 (read-back).
    assert sorted(r.idle) == sorted([("get_batch_device", 12),
                                     ("step_dispatch", 3),
                                     ("loss_readback", 10),
                                     ("loss_readback", 5)])
    b = r.breakdown(top=2)
    assert b["idle_gaps"] == [["get_batch_device", 12e-9],
                              ["loss_readback", 10e-9]]
    assert b["device_ops"][0] == ["fusion", pytest.approx(47e-9)]


def test_nested_ops_count_their_self_time_once():
    ops = [Op(0, "p/while", "p", 0, 100), Op(0, "p/fusion", "p", 10, 30),
           Op(0, "p/fusion", "p", 40, 60)]
    r = trace.reduce(ops, [Span("step_dispatch", 0, 100)], devices=1)
    assert r.busy_ns == [100]
    assert r.op_seconds() == pytest.approx({"p/while": 60e-9,
                                            "p/fusion": 40e-9})


def test_stable_names_drop_hashes_and_numbers():
    assert trace.stable_name("jit_train_step(13197302091856079018)",
                             "%fusion.478.remat3 = (f32[8,3,2048]{2,1,0}") \
        == "jit_train_step/fusion.remat"
    assert trace.stable_name("jit_reassemble_window(98)",
                             "%reassemble_window.1 = (s32[8,128]") \
        == "jit_reassemble_window/reassemble_window"


RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "small.xplane.pb")
SPANS = ("get_batch_device", "step_dispatch", "loss_readback", "device_put")


def test_recorded_tpu_trace():
    """A trace taken on a TPU v5 lite (source-file metadata stripped): three
    steps of (device_put of a 1,032-token window, its reassembly, a
    1024 x 1024 bf16 matmul, the result read back), then a 64 MiB
    device_put; the numbers below were read off its events by hand."""
    r = trace.reduce(*trace.load(RECORDED, SPANS), devices=1)
    # First get_batch_device span start to the device_put span's end.
    assert r.window == (47_657_065, 47_657_065 + 121_418_539)
    # The first step's device work shows 0.2 ms before its span opens (the
    # device clock leads): the window holds two steps' ops, 19,108 ns each.
    assert r.busy_ns == [38_216]
    # Reassembly program runs: reshape + kernel, 428 + 322, 648 + 321 and
    # 435 + 321 ns, all three counted.
    assert r.module_seconds(("reassemble",)) == pytest.approx(2_475e-9)
    names = set(r.op_seconds())
    assert names == {"jit_reassemble_window/reshape",
                     "jit_reassemble_window/reassemble_window",
                     "jit__lambda/copy-start", "jit__lambda/copy-done",
                     "jit__lambda/fusion"}
    gaps = r.breakdown()["idle_gaps"]
    assert gaps[0] == ["device_put", pytest.approx(0.113029809)]
    assert [g[0] for g in gaps[1:3]] == ["loss_readback", "get_batch_device"]


def test_no_span_means_no_window():
    with pytest.raises(ValueError):
        trace.reduce(synthetic()[0], [], devices=1)


def test_phi4_step_flops_by_the_palm_convention():
    with open(os.path.join(CONFIGS, "phi4mini-train4k.json")) as f:
        c = json.load(f)
    # Per layer: q and o 3072 x 3072 each, k and v 3072 x 1024 each, MLP
    # 3 x 3072 x 8192; tied head 25,008 x 3072.
    per_layer = 2 * 3072 * 3072 + 2 * 3072 * 1024 + 3 * 3072 * 8192
    assert flops.matmul_params(c) == 4 * per_layer + 25008 * 3072 \
        == 479_477_760
    tokens = 8 * 4096
    attn = 12 * 4 * 24 * 128 * 4096
    want = (6 * 479_477_760 + attn) * tokens
    assert flops.train_step_flops(c, 8, 4096) == want
    assert want == pytest.approx(1.1406e14, rel=1e-4)


def test_assemble_bytes_from_shapes():
    # 8 x 4,097 uint32 tokens in, 8 x 4,096 inputs and labels out.
    assert flops.assemble_bytes(8, 4096) == 4 * (8 * 4097 + 2 * 8 * 4096) \
        == 393_248
