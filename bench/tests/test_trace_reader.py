"""The reduction from a profiler trace to device numbers, on synthetic
events and on two small traces recorded on a TPU v5 lite."""
import json
import os

import pytest

import trace_reader as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def recorded(name):
    with open(os.path.join(DATA, f"trace_{name}_s16.json")) as f:
        return json.load(f)


def test_union_and_gaps():
    spans = [(10, 30), (20, 40), (60, 70), (65, 66)]
    assert tr.union_length(spans) == 40
    assert list(tr.gaps(spans, 0, 100)) == [(0, 10), (40, 60), (70, 100)]
    assert tr.union_length([]) == 0


def test_parse_op_reads_instruction_opcode_and_callee():
    text = ("%fusion.15 = f32[65536]{0:T(1024)S(1)} fusion(s32[1818532]{0:T(1024)} %a, "
            "f32[]{:T(128)} %c), kind=kCustom, calls=%fused_computation.8.clone.clone")
    assert tr.parse_op(text) == ("fusion.15", "fusion", "fused_computation.8.clone.clone")
    loop = "%while.2 = (pred[]{:T(512)}, s32[]{:T(128)}) while((pred[]{:T(512)}) %t), condition=%c"
    assert tr.parse_op(loop)[1] == "while"
    assert tr.parse_op("%min.3 = s32[8]{0} minimum(s32[8]{0} %a, s32[8]{0} %b)")[1] == "minimum"


def test_classes_follow_the_fused_computation_and_its_callees():
    hlo = "\n".join([
        "%inner (p: f32[4]) -> f32[4] {",
        "  ROOT %scatter-add.1 = f32[4]{0} scatter(%p, %i, %u), to_apply=%add",
        "}",
        "%outer (p: f32[4]) -> f32[4] {",
        "  ROOT %fusion.2 = f32[4]{0} fusion(%p), kind=kLoop, calls=%inner",
        "}",
        "%g (p: f32[4], i: s32[8]) -> f32[8] {",
        "  ROOT %gather.3 = f32[8]{0} gather(%p, %i), slice_sizes={1}",
        "}",
    ])
    comps = tr.computation_opcodes(hlo)
    assert tr.op_class("fusion", "outer", comps) == "scatter"
    assert tr.op_class("fusion", "g", comps) == "gather"
    assert tr.op_class("scatter", "", comps) == "scatter"
    assert tr.op_class("add", "", comps) == "other"


def test_reduce_synthetic_window():
    events = {
        "host": [["bench.window", 0, 100], ["bench.run", 0, 50], ["bench.run", 50, 50],
                 ["PjitFunction(f)", 45, 20]],
        "device": {"/device:TPU:0": [
            ["while.1", "while", "", 0, 100],          # a container: not busy on its own
            ["fusion.1", "fusion", "g", 10, 20],
            ["scatter.2", "scatter", "", 20, 20],
            ["add.3", "add", "", 60, 10],
            ["copy.4", "copy", "", 90, 30],             # clipped at the window's end
        ]},
    }
    hlo = "%g (p: f32[4]) -> f32[8] {\n  ROOT %gather.1 = f32[8]{0} gather(%p, %i)\n}"
    r = tr.reduce(events, hlo)
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx(50e-9)           # [10,40] + [60,70] + [90,100]
    assert r["class_s"]["gather"] == pytest.approx(20e-9)
    assert r["class_s"]["scatter"] == pytest.approx(20e-9)
    assert r["class_s"]["other"] == pytest.approx(20e-9)
    gaps = dict(r["breakdown"]["idle_gaps"])
    assert gaps["bench.run"] == pytest.approx(30e-9)     # [0,10] and [70,90]
    assert gaps["PjitFunction(f)"] == pytest.approx(20e-9)   # [40,60]
    assert r["breakdown"]["device_ops"][0][0] in ("fusion.1 (gather)", "scatter.2 (scatter)")


def test_reduce_without_window_fails():
    with pytest.raises(ValueError):
        tr.reduce({"host": [], "device": {}})


@pytest.mark.parametrize("name,scatter_ops,gather_ops,loops,busy_s,class_s", [
    ("pr", ["fusion.15"], ["fusion.13", "fusion.14"], {"while": 2}, 0.671990397,
     {"other": 0.000327025, "gather": 0.417056676, "scatter": 0.254606696}),
    ("sssp", ["fusion.4", "fusion.5"], ["fusion", "fusion.1", "fusion.2", "fusion.3"],
     {"while": 2, "conditional": 20}, 0.797677527,
     {"other": 0.000485055, "gather": 0.499562929, "scatter": 0.297629543}),
])
def test_recorded_tpu_trace(name, scatter_ops, gather_ops, loops, busy_s, class_s):
    """Read by hand: in pr.sp the segment sum is a kCustom fusion around a
    scatter, the two edge-sized gathers (rank and out-degree of each
    in-edge's source) are fusions around a gather; sssp.sp adds the push
    scatter-min and the pull segment-min, and gathers of the frontier mask
    and of the distances in each direction. Everything else is small. Two
    runs: two `while` ops, and in sssp one `conditional` per superstep."""
    rec = recorded(name)
    r = tr.reduce(rec["events"], rec["hlo"])
    ops = dict(r["breakdown"]["device_ops"])
    want = {f"{op} (scatter)" for op in scatter_ops} | {f"{op} (gather)" for op in gather_ops}
    top = {k for k, _ in r["breakdown"]["device_ops"][:len(want)]}
    assert top == want
    assert r["class_s"]["scatter"] == pytest.approx(
        sum(ops[f"{op} (scatter)"] for op in scatter_ops))
    assert r["class_s"]["gather"] == pytest.approx(
        sum(ops[f"{op} (gather)"] for op in gather_ops))
    # one while_loop per run keeps the device busy nearly all the window
    assert 0.98 < r["busy_s"] / r["window_s"] <= 1.0
    assert sum(r["class_s"].values()) >= r["busy_s"] * 0.999
    assert r["control_ops"] == loops
    # one chip, no collective: the numbers read before collectives had a class
    assert r["chips"] == 1 and r["collective_exposed_s"] == 0
    assert r["busy_s"] == pytest.approx(busy_s, rel=1e-12)
    assert r["class_s"] == pytest.approx(class_s, rel=1e-12)


def test_collectives_class_before_gather_and_scatter():
    hlo = "\n".join([
        "%ag (p: f32[4]) -> f32[16] {",
        "  ROOT %all-gather.1 = f32[16]{0} all-gather(%p), dimensions={0}",
        "}",
        "%mixed (p: f32[16], i: s32[8]) -> f32[8] {",
        "  %f = f32[16]{0} fusion(%p), kind=kLoop, calls=%ag",
        "  ROOT %gather.2 = f32[8]{0} gather(%f, %i), slice_sizes={1}",
        "}",
    ])
    comps = tr.computation_opcodes(hlo)
    for opcode in ("all-gather", "all-gather-start", "all-gather-done", "all-reduce",
                   "all-reduce-start", "all-reduce-done", "reduce-scatter", "all-to-all",
                   "collective-permute", "collective-permute-start",
                   "collective-permute-done"):
        assert tr.op_class(opcode, "", comps) == "collective", opcode
    assert tr.op_class("fusion", "ag", comps) == "collective"
    assert tr.op_class("fusion", "mixed", comps) == "collective"
    assert tr.op_class("all-gathers", "", comps) == "other"


def test_exposed_collective_time():
    """Two devices. On the first, an async all-gather's halves and a fusion
    around an all-gather, partly covered by compute; on the second, a
    collective no compute covers. Times are means over the two."""
    hlo = "%ag (p: f32[4]) -> f32[16] {\n  ROOT %all-gather.1 = f32[16]{0} all-gather(%p)\n}"
    events = {
        "host": [["bench.window", 0, 100]],
        "device": {
            "/device:TPU:0": [
                ["all-gather-start.1", "all-gather-start", "", 0, 10],
                ["add.1", "add", "", 5, 30],                      # covers [5, 35]
                ["all-gather-done.1", "all-gather-done", "", 30, 10],   # exposed [35, 40]
                ["fusion.2", "fusion", "ag", 50, 20],             # exposed [50, 60]
                ["scatter.3", "scatter", "", 60, 20],
            ],
            "/device:TPU:1": [
                ["all-reduce.4", "all-reduce", "", 0, 40],        # all exposed
                ["gather.5", "gather", "", 40, 10],
            ],
        },
    }
    r = tr.reduce(events, hlo)
    assert r["chips"] == 2
    assert r["collective_exposed_s"] == pytest.approx((5 + 5 + 10 + 40) / 2 * 1e-9)
    assert r["class_s"]["collective"] == pytest.approx((10 + 10 + 20 + 40) / 2 * 1e-9)
    assert r["class_s"]["other"] == pytest.approx(30 / 2 * 1e-9)
    assert r["busy_s"] == pytest.approx((70 + 50) / 2 * 1e-9)
    assert dict(r["breakdown"]["device_ops"])["fusion.2 (collective)"] == pytest.approx(10e-9)
