"""The reduction from trace to numbers: on a hand-made trace, where the
answer is known by construction, and on a small trace recorded on the
v5e (PR 22), where it is pinned."""

import os

import pytest

from benchmark import trace_reduce as tr

MS = 1_000_000      # ns


def _plane(ops, modules=()):
    return {tr.OPS_LINE: [(n, s * MS, d * MS) for n, s, d in ops],
            tr.MODULES_LINE: [(n, s * MS, d * MS) for n, s, d in modules]}


def _hlo(name, op, extra=""):
    return f"%{name} = bf16[8,128]{{1,0:T(8,128)(2,1)}} {op}(bf16[8,128]{{1,0}} %x){extra}"


KERNEL = _hlo("closed_call.7", "custom-call",
              ', custom_call_target="tpu_custom_call"')


def test_busy_idle_gaps_own_time_and_mosaic():
    plane = _plane(
        ops=[(_hlo("while.1", "while"), 0, 15),             # spans its body
             (_hlo("fusion.1", "fusion"), 0, 10),
             (_hlo("fusion.2", "fusion"), 10, 4),           # 1 ms is the while's own
             (KERNEL, 20, 10),                              # gap 15-20, busy 20-30
             (_hlo("custom-call.3", "custom-call",
                   ', custom_call_target="AllocateBuffer"'), 40, 10)],  # gap 30-40
        modules=[("jit_tick(123)", 0, 30), ("jit_prefill(9)", 40, 10)])
    out = tr.reduce({"/device:TPU:0": plane})
    assert out["chips"] == 1
    assert out["window_s"] == pytest.approx(0.050)
    assert out["busy_s"] == pytest.approx(0.035)
    assert out["mosaic_s"] == pytest.approx(0.010)
    assert dict(out["device_ops"]) == pytest.approx({
        "fusion.1 bf16[8,128]": 0.010, "fusion.2 bf16[8,128]": 0.004,
        "while.1 bf16[8,128]": 0.001,
        "closed_call.7 custom-call mosaic bf16[8,128]": 0.010,
        "custom-call.3 bf16[8,128]": 0.010})
    # A gap is named after the program the device ran next.
    assert dict(out["idle_gaps"]) == pytest.approx(
        {"before:jit_tick": 0.005, "before:jit_prefill": 0.010})


def test_exposed_collective_time_is_what_no_compute_covers():
    plane = _plane(ops=[
        (_hlo("while.9", "while"), 0, 40),      # a container covers nothing
        (_hlo("fusion.1", "fusion"), 0, 10),
        (_hlo("all-gather-start.1", "all-gather-start"), 10, 1),   # exposed
        (_hlo("fusion.2", "fusion"), 11, 9),
        (_hlo("all-gather-done.1", "all-gather-done"), 20, 6),     # exposed wait
        (_hlo("reduce-scatter.4", "reduce-scatter"), 26, 4),       # synchronous
        (_hlo("fusion.3", "fusion"), 28, 12),                      # covers 28-30
    ])
    out = tr.reduce({"/device:TPU:0": plane})
    assert out["collective_s"] == pytest.approx(0.011)
    assert out["collective_exposed_s"] == pytest.approx(0.009)


def test_opcode_and_label_read_instruction_text():
    text = ("%all-gather-start.3 = (bf16[4,8]{1,0:T(8,128)(2,1)}, bf16[16,8]"
            "{1,0:T(8,128)(2,1)S(1)}) all-gather-start(bf16[4,8]{1,0} %p), "
            "replica_groups={{0,1,2,3}}")
    assert tr.opcode(text) == "all-gather-start"
    assert tr.opcode(text) in tr.COLLECTIVES
    assert tr.label(text) == "all-gather-start.3 bf16[4,8]"
    assert tr.opcode(KERNEL) == "custom-call" and tr.MOSAIC_TARGET in KERNEL
    assert tr.opcode(_hlo("all-gather-fusion.1", "fusion")) not in tr.COLLECTIVES
    # Not instruction text: the name stands for itself.
    assert tr.opcode("all-reduce.12") == "all-reduce"
    assert tr.label("fusion.3") == "fusion.3"


def test_chips_are_averaged_and_empty_planes_left_out():
    a = _plane(ops=[("fusion.1", 0, 10)])
    b = _plane(ops=[("fusion.1", 0, 10), ("fusion.2", 20, 10)])
    out = tr.reduce({"/device:TPU:0": a, "/device:TPU:1": b,
                     "/device:TPU:2": _plane(ops=[])})
    assert out["chips"] == 2
    assert out["busy_s"] == pytest.approx(0.015)
    assert out["window_s"] == pytest.approx(0.020)
    assert tr.reduce({})["chips"] == 0


RECORDED = os.path.join(os.path.dirname(__file__),
                        "serve_prefill_heavy.v5e.xplane.pb")


def test_recorded_v5e_trace():
    """140 ms of ``serve_prefill_heavy`` on a TPU v5 lite (PR 22): one
    1 x 1024 prefill and the ticks around it, cut from a traced run to
    the chip's two lines. Pins the file format (instruction text as the
    event name, nesting under ``while``, the Mosaic target) and the
    arithmetic."""
    planes = tr.load(RECORDED)
    assert list(planes) == ["/device:TPU:0"]
    ops = planes["/device:TPU:0"][tr.OPS_LINE]
    assert len(ops) == 3625 and len(planes["/device:TPU:0"][tr.MODULES_LINE]) == 4
    whiles = [e for e in ops if tr.opcode(e[0]) == "while"]
    assert whiles and all(e[0].startswith("%while") for e in whiles)
    out = tr.reduce(planes)
    assert out["chips"] == 1
    assert out["window_s"] == pytest.approx(0.139906224, rel=1e-6)
    assert out["busy_s"] == pytest.approx(0.118697667, rel=1e-6)
    assert out["mosaic_s"] == pytest.approx(0.003717146, rel=1e-6)
    assert out["collective_s"] == 0.0
    ops_by_label = dict(out["device_ops"])
    # The paged decode kernel, and the arena slab copies PERF.md asked about.
    assert ops_by_label["closed_call.14 custom-call mosaic bf16[8,8,4,128]"] \
        == pytest.approx(out["mosaic_s"])
    assert "copy_bitcast_fusion.20 fusion bf16[145,8,64,128]" in ops_by_label
    # Own times add up to busy time: nothing is counted twice.
    own = sum(t for _, t, _ in tr.self_times([e for e in ops if e[2] > 0]))
    assert own / 1e9 == pytest.approx(out["busy_s"], rel=1e-3)
    gaps = dict(out["idle_gaps"])
    assert gaps["before:jit_tick"] == pytest.approx(0.00837884, rel=1e-5)
    assert sum(gaps.values()) == pytest.approx(out["window_s"] - out["busy_s"])
