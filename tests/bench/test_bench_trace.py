"""The reduction from a trace to busy time, idle attribution and step
times: on synthetic events, and on a trimmed real v5e trace."""
import gzip
import json

import pytest

from bench import spec, trace

FIX = spec.ROOT / "tests" / "bench" / "fixtures"


def _events():
    # a window [0, 100) ns; ops busy [10,30) [25,40) [60,70); two steps
    return {
        "host": [["bench.window", 0, 100],
                 ["bench.dispatch.0", 5, 5], ["bench.sync", 10, 30],
                 ["bench.sample", 40, 10], ["bench.schedule", 50, 5],
                 ["bench.dispatch.1", 55, 5], ["bench.sync", 60, 10],
                 ["bench.sample", 70, 30]],
        "modules": [["jit_step(2)", 10, 30], ["jit_step(0)", 60, 10],
                    ["jit_other", 80, 5]],
        "ops": [["custom-call.1", 10, 12, True], ["fusion.2", 25, 15, False],
                ["custom-call.1", 60, 4, True], ["fusion.3", 64, 6, False]],
    }


def test_union_merges_and_clips():
    ivs = [["a", 10, 20], ["b", 25, 15], ["c", 90, 20]]
    assert trace.union(ivs, 0, 100) == [(10, 40), (90, 100)]
    assert trace.idle_gaps([(10, 40), (90, 100)], 0, 100) == \
        [(0, 10), (40, 90)]


def test_reduce_busy_idle_and_attribution():
    red = trace.reduce(_events(), [{"n": 2, "pos": [], "chunks": []},
                                   {"n": 0, "pos": [], "chunks": []}])
    assert red["window_s"] == pytest.approx(100e-9)
    # busy: [10,22) [25,40) [60,70)
    assert red["busy_s"] == pytest.approx(37e-9)
    idle = dict(red["idle_gaps"])
    # gaps: [0,10) under dispatch.0 (and nothing else); [22,25) under
    # sync; [40,60) under sample (10), schedule (5), dispatch.1 (5): the
    # span overlapping most names it; [70,100) under sample
    assert idle["dispatch"] == pytest.approx(10e-9)
    assert idle["sync"] == pytest.approx(3e-9)
    assert idle["sample"] == pytest.approx(50e-9)
    assert sum(idle.values()) == pytest.approx(63e-9)
    top = dict(red["device_ops"])
    assert top["custom-call.1"] == pytest.approx(16e-9)


def test_steps_match_dispatches_in_order():
    red = trace.reduce(_events(), [{"n": 2, "pos": [], "chunks": []},
                                   {"n": 0, "pos": [], "chunks": []}])
    steps = red["steps"]
    assert [s["n"] for s in steps] == [2, 0]
    assert steps[0]["device_s"] == pytest.approx(30e-9)
    assert steps[0]["pallas_s"] == pytest.approx(12e-9)
    assert steps[1]["pallas_s"] == pytest.approx(4e-9)


def test_modules_before_the_first_traced_dispatch_are_dropped():
    ev = _events()
    ev["modules"].insert(0, ["jit_step(0)", 0, 4])   # launched untraced
    red = trace.reduce(ev, [{"n": 2, "pos": [], "chunks": []},
                            {"n": 0, "pos": [], "chunks": []}])
    assert [s["device_s"] for s in red["steps"]] == \
        pytest.approx([30e-9, 10e-9])


def test_recorded_v5e_trace():
    """Two steps of granite2b-chat recorded on a TPU v5e: a step
    with one prompt chunk, then a decode step; host spans on the same
    clock."""
    from bench import model
    with gzip.open(FIX / "v5e-granite2b-chat-trace.json.gz", "rt") as fh:
        fx = json.load(fh)
    red = trace.reduce(fx["events"], fx["records"])
    steps = red["steps"]
    assert [s["n"] for s in steps] == [1, 0]
    assert steps[0]["device_s"] == pytest.approx(0.123425299)
    assert steps[1]["device_s"] == pytest.approx(0.09410999)
    for s in steps:
        assert 0 < s["pallas_s"] < s["device_s"]
    assert 0 < red["busy_s"] < red["window_s"]
    assert set(dict(red["idle_gaps"])) <= {"schedule", "dispatch", "sync",
                                          "sample", "none"}
    assert any("custom-call" in n for n, _ in red["device_ops"])
    c = spec.load_json(spec.BENCH_DIR / "configs" / "granite-3-2b.json")
    for s in steps:
        flops, nbytes = model.step_work(c, s["pos"], s["chunks"])
        least = max(flops / 197e12, nbytes / 819e9)
        assert 0 < least < s["device_s"]


READ = spec.load_json(FIX / "granite-readings.json")["readers"]


@pytest.mark.parametrize("fixture,config,metric", [
    (fx, cfg, m)
    for fx, cfg in (("v5e-granite2b-chat-trace.json.gz", "granite-3-2b"),
                    ("v5e-granite8b-chat-program.json.gz",
                     "granite-3.0-8b-l20"))
    for m in ("glue_share", "step_roofline", "device_idle_share",
              "step_mfu")])
def test_one_chip_readers_read_as_before(fixture, config, metric):
    """The one-chip readers give the recorded traces the values they gave
    before the trace reduction learned of several chips."""
    from bench import model
    with gzip.open(FIX / fixture, "rt") as fh:
        fx = json.load(fh)
    red = trace.reduce(fx["events"], fx["records"])
    c = spec.load_json(spec.BENCH_DIR / "configs" / f"{config}.json")
    for st in red["steps"]:
        st["flops"], st["bytes"] = model.step_work(c, st["pos"], st["chunks"])
    ctx = {"steps": red["steps"], "window_s": red["window_s"],
           "busy_s": red["busy_s"], "chip_busy_s": red["chip_busy_s"],
           "chips": 1,
           "peaks": spec.load_json(spec.BENCH_DIR / "peaks.json")[
               "TPU v5 lite"]}
    assert spec.load_metric_reader(metric)(ctx) == READ[fixture][metric]


def test_reduce_reads_every_chip_and_the_collectives():
    ev = _events()
    ev["ops"] += [["%all-reduce.7 = bf16[32,4096] all-reduce", 30, 4, False],
                  ["%all-gather-start.1 = bf16[8] all-gather-start", 66, 2,
                   False]]
    # chip 0 as ``load`` merges it, and a second chip busy [0, 50)
    ev["chip_busy"] = [[[10, 22], [25, 40], [60, 70]], [[0, 50]]]
    red = trace.reduce(ev, [{"n": 2, "pos": [], "chunks": []},
                            {"n": 0, "pos": [], "chunks": []}])
    assert red["chip_busy_s"] == pytest.approx([37e-9, 50e-9])
    assert [s["collective_s"] for s in red["steps"]] == \
        pytest.approx([4e-9, 2e-9])
    # ``load`` merges a chip's leaf ops; a loop's own event is no leaf
    from types import SimpleNamespace as E
    evs = [E(name="%a = f32[2] fusion(%x)", start_ns=10, duration_ns=12),
           E(name="%w = f32[2] while(%x)", start_ns=0, duration_ns=90),
           E(name="%b = f32[2] fusion(%x)", start_ns=20, duration_ns=20),
           E(name="%c = f32[2] all-reduce(%x)", start_ns=60, duration_ns=5)]
    assert trace._merged(evs) == [[10, 40], [60, 65]]
    assert trace.is_collective("%x = f32[2] all-reduce-done")
    assert not trace.is_collective("%fusion.3 = f32[2] fusion")


@pytest.mark.parametrize("metric,want", [
    ("collective_share", 100 * 6 / 40), ("mesh_idle_share", 100 * 13 / 200),
    ("mesh_step_mfu", 100 * 8e12 / (40e-3 * 4 * 197e12)),
    # both steps bound by FLOPs: 6e12 / 4 peaks > 3e9 / 4 HBMs
    ("mesh_step_roofline", 100 * (8e12 / (4 * 197e12)) / 40e-3)])
def test_mesh_readers(metric, want):
    ctx = {"steps": [{"device_s": 30e-3, "collective_s": 4e-3,
                      "flops": 6e12, "bytes": 3e9},
                     {"device_s": 10e-3, "collective_s": 2e-3,
                      "flops": 2e12, "bytes": 1e9}],
           "window_s": 50.0, "chip_busy_s": [44.0, 46.0, 47.0, 50.0],
           "chips": 4,
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    assert spec.load_metric_reader(metric)(ctx) == pytest.approx(want)
    # a one-chip trace, or one with no steps, reads nothing
    quiet = dict(ctx, steps=[], chip_busy_s=[])
    assert spec.load_metric_reader(metric)(quiet) is None
