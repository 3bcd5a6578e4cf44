"""The program's spans and launch names read from a trace
(``bench/phases.py``): the clock alignment, idle time by host phase and
the three readers, on synthetic events; loading spans from a real
profile; and a trace of a program without spans, which reads nothing."""
import pytest

from bench import phases


def _events():
    """Two steps on the host clock; their modules on a device clock that
    reads 2-12 ns behind (offset interval [2, 12])."""
    program = [
        ["serve.step", 100, 200, {"step_num": 4}],
        ["serve.admit", 100, 10, {}],
        ["serve.stage", 110, 10, {}],
        ["serve.dispatch", 120, 10, {"step": 4, "chunks": 1, "active": 2}],
        ["serve.sync", 130, 120, {}],
        ["serve.sample", 250, 20, {}],
        ["serve.sync", 270, 10, {}],
        ["serve.first_token", 280, 10, {"rids": 3}],
        ["serve.step", 300, 120, {"step_num": 5}],
        ["serve.admit", 300, 5, {}],
        ["serve.stage", 305, 5, {}],
        ["serve.dispatch", 310, 10, {"step": 5, "chunks": 0, "active": 3}],
        ["serve.sync", 320, 80, {}],
        ["serve.sample", 400, 20, {}],
    ]
    return {
        "host": [],
        "program": program,
        "modules": [["jit_step(1)", 118, 120], ["jit_step(0)", 310, 75]],
        "ops": [["%decode_attn_a.1 = f32[2] custom-call", 118, 40, True],
                ["%fusion.1 = f32[2] fusion", 158, 72, False],
                ["%ffn.2 = f32[2] custom-call", 230, 8, True],
                ["%decode_attn_b.3 = f32[2] custom-call", 310, 20, True],
                ["%fusion.4 = f32[2] fusion", 330, 55, False]],
        "launches": {
            "%decode_attn_a.1 = f32[2] custom-call":
                "decode_attn_B2+prefill_attn0_C8",
            "%ffn.2 = f32[2] custom-call": "ffn_proj→decode_act",
            "%decode_attn_b.3 = f32[2] custom-call": "decode_attn_B2"},
    }


def test_steps_group_phases_under_their_step():
    st = phases.steps(_events())
    assert [s["step"] for s in st] == [4, 5]
    assert [p[0] for p in st[0]["phases"]] == [
        "admit", "stage", "dispatch", "sync", "sample", "sync",
        "first_token"]
    assert st[0]["dispatch"][2]["chunks"] == 1
    assert st[0]["syncs"] == [[130, 250], [270, 280]]


def test_align_bounds_the_offset_from_both_sides():
    ev = _events()
    al = phases.align(ev, phases.steps(ev))
    assert (al["lo_ns"], al["hi_ns"]) == (2, 12)
    assert al["offset_ns"] == 7 and al["width_ns"] == 10
    assert [m[0] for _, m in al["pairs"]] == ["jit_step(1)", "jit_step(0)"]
    off = al["offset_ns"]
    for st, (_, s, d) in al["pairs"]:
        assert st["dispatch"][0] <= s + off
        assert s + d + off <= st["syncs"][0][1]


def test_align_takes_the_pairing_needing_least_offset():
    """Decode steps look alike: one step off also leaves an interval, but
    it needs a step's length of offset.  A module running before the
    first traced dispatch is left unpaired."""
    program, modules = [], [["jit_step(0)", -90, 80]]
    for i in range(3):
        t = 100 * i
        program += [["serve.step", t, 100, {"step_num": i}],
                    ["serve.dispatch", t + 10, 10,
                     {"step": i, "chunks": 0, "active": 4}],
                    ["serve.sync", t + 20, 75, {}]]
        modules.append(["jit_step(0)", t + 10, 80])
    ev = {"program": program, "modules": modules, "ops": []}
    al = phases.align(ev, phases.steps(ev))
    assert (al["lo_ns"], al["hi_ns"]) == (0, 5)
    assert [m[1] for _, m in al["pairs"]] == [10, 110, 210]


def test_align_refuses_a_module_with_two_chunk_counts():
    ev = _events()
    ev["modules"][1][0] = "jit_step(1)"       # both steps one executable
    assert phases.align(ev, phases.steps(ev)) is None


def test_idle_by_phase_splits_each_gap_over_the_phases():
    ev = _events()
    al = phases.align(ev, phases.steps(ev))
    idle = phases.idle_by_phase(ev, al)
    # the one gap, device [238, 310) = host [245, 317)
    want = {"sync": 15, "sample": 20, "first_token": 10, "step": 10,
            "admit": 5, "stage": 5, "dispatch": 7}
    assert idle == pytest.approx({k: v * 1e-9 for k, v in want.items()})
    assert "none" not in idle


def test_idle_outside_every_step_reads_none():
    ev = _events()
    ev["program"] = [p for p in ev["program"]
                     if not (p[0] == "serve.step" and p[1] == 300)
                     and p[1] < 300] + [
        ["serve.step", 310, 110, {"step_num": 5}],
        ["serve.dispatch", 310, 10, {"step": 5, "chunks": 0, "active": 3}],
        ["serve.sync", 320, 80, {}]]
    al = phases.align(ev, phases.steps(ev))
    idle = phases.idle_by_phase(ev, al)
    assert idle["none"] == pytest.approx(10e-9)        # host [300, 310)


def test_readers():
    ev = _events()
    al = phases.align(ev, phases.steps(ev))
    # steps less their syncs: 200 - 130 and 120 - 80 ns
    assert phases.host_loop_ms(ev) == pytest.approx(55e-6)
    # idle inside the syncs: 5 + 10 (step 4), 8 (step 5)
    assert phases.sync_gap_ms(ev, al) == pytest.approx(11.5e-6)
    # launches with a decode_attn member: 40 + 20 of 120 + 75 ns
    assert phases.attention_share(ev, al) == pytest.approx(100 * 60 / 195)
    assert phases.unnamed_launches(ev, al) == 0
    ev["launches"].pop("%ffn.2 = f32[2] custom-call")
    assert phases.unnamed_launches(ev, al) == 1


def test_a_program_without_spans_reads_nothing():
    ev = _events()
    del ev["program"], ev["launches"]
    al = phases.align(ev, phases.steps(ev))
    assert al is None
    assert phases.host_loop_ms(ev) is None
    assert phases.sync_gap_ms(ev, al) is None
    assert phases.attention_share(ev, al) is None
    rep = phases.report(ev)
    assert rep["matched"] == 0 and rep["idle_by_phase"] == {}


@pytest.mark.parametrize("text, launch", [
    ('%decode_norm1_qkv_proj_decode_attn.1 = f32[8,128] custom-call(%a), '
     'custom_call_target="tpu_custom_call", operand_layout_constraints='
     '{f32[8,128]{1,0}}, frontend_attributes={kernel_metadata={\n'
     '"launch":"decode_norm1\\u2192qkv_proj+decode_attn"\n}}, metadata='
     '{op_name="x"}', "decode_norm1→qkv_proj+decode_attn"),
    ('%closed_call.21 = bf16[24,2048] custom-call(%a), custom_call_target='
     '"tpu_custom_call", frontend_attributes={kernel_metadata={}}', None),
    ('%fusion.3 = f32[2] fusion(%a), kind=kLoop', None),
    ('%x = f32[2] custom-call(%a), frontend_attributes={kernel_metadata='
     '{"launch":"a}b\\"c"}}', 'a}b"c'),
])
def test_launch_name_from_op_text(text, launch):
    assert phases._launch(text) == launch


def test_load_reads_program_spans_from_a_profile(tmp_path):
    import jax
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.StepTraceAnnotation("serve.step", step_num=7):
            with jax.profiler.TraceAnnotation("serve.dispatch", step=7,
                                              chunks=2, active=5):
                pass
            with jax.profiler.TraceAnnotation("serve.first_token",
                                              rids="3 4"):
                pass
    got = phases.load(str(tmp_path))
    assert got["launches"] == {}
    names = [p[0] for p in got["program"]]
    assert names == ["serve.step", "serve.dispatch", "serve.first_token"]
    args = [p[3] for p in got["program"]]
    assert args == [{"step_num": 7}, {"step": 7, "chunks": 2, "active": 5},
                    {"rids": "3 4"}]
    st = phases.steps(got)
    assert st[0]["step"] == 7 and st[0]["dispatch"][2]["chunks"] == 2


def test_a_traced_harness_run_keeps_the_program_spans():
    """A traced run of the harness at the CPU test size (no device plane
    there): the program's spans reach the kept events beside the bench's
    own, and the host-side reader reads them."""
    import jax

    import bench.run as R
    from bench import spec
    fix = spec.ROOT / "tests" / "bench" / "fixtures"
    bm = spec.load_benchmark()
    cell = spec.Cell(
        name="granite2b-chat", config_name="tiny-granite",
        traffic_name="tiny-chat", chips=1,
        config=spec.load_json(fix / "tiny-granite.json"),
        traffic=spec.load_json(fix / "tiny-chat.json"),
        params={"rate_per_s": 8.0, "logit_gap_mean_limit": 1.0},
        per_layer=bm["per_layer"])
    args = R.parse(["--workload", "x", "--seed", str(2 ** 31 + 5),
                    "--seconds", "2", "--trace", "1"])
    peaks = spec.load_json(spec.BENCH_DIR / "peaks.json")["TPU v5 lite"]
    with phases.keeping_program_spans() as kept:
        R.run(cell, args, peaks, jax.devices())
    ev = kept["events"]
    assert any(h[0].startswith("bench.dispatch") for h in ev["host"])
    st = [s for s in phases.steps(ev) if s["dispatch"]]
    assert st and all(s["syncs"] for s in st)
    assert len(st) >= len(kept["records"])
    assert phases.host_loop_ms(ev) > 0


def test_recorded_v5e_trace_with_program_spans():
    """Three steps of granite8b-chat recorded on a TPU v5e by
    ``bench/phases.py --save`` (two with a prompt chunk, then a decode
    step; trimmed to them): the clocks align, every matched module lies
    inside its host window, the program's phases hold the device's idle
    time, every Pallas launch is named, and all three readers read."""
    import gzip
    import json

    from bench import spec, trace
    path = spec.ROOT / "tests" / "bench" / "fixtures" \
        / "v5e-granite8b-chat-program.json.gz"
    with gzip.open(path, "rt") as fh:
        fx = json.load(fh)
    ev = fx["events"]
    prog = phases.steps(ev)
    assert [s["dispatch"][2]["chunks"] for s in prog] == [1, 1, 0]
    al = phases.align(ev, prog)
    assert al["lo_ns"] <= al["offset_ns"] <= al["hi_ns"]
    assert 0 < al["width_ns"] < 5e6
    for st, (_, s, d) in al["pairs"]:
        assert st["dispatch"][0] <= s + al["offset_ns"]
        assert s + d + al["offset_ns"] <= st["syncs"][0][1]
    # the harness's own matching finds the same modules
    red = trace.reduce(ev, fx["records"])
    assert [s["device_s"] for s in red["steps"]] == \
        pytest.approx([m[2] * 1e-9 for _, m in al["pairs"]])
    idle = phases.idle_by_phase(ev, al)
    assert idle.get("none", 0) < 0.05 * sum(idle.values())
    assert {"sync", "dispatch", "stage", "sample"} <= set(idle)
    assert phases.unnamed_launches(ev, al) == 0
    assert any("+prefill_attn0_" in v for v in ev["launches"].values())
    assert 1 < phases.host_loop_ms(ev) < 20
    assert 0 < phases.sync_gap_ms(ev, al) < 10
    assert 0 < phases.attention_share(ev, al) < 50
