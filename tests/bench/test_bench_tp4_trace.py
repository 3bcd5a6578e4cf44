"""The trace reduction and the four mesh readers on a recorded four-chip
trace: three steps of granite8b-tp4-chat on a TPU v5e 2x2 (one with two
prompt chunks, two decode steps), cut from the traced window of a
``--save-trace`` run at 2.4 req/s, with a map from each all-reduce of the
compiled steps to the named scope (``tp_attn_out_psum`` /
``tp_ffn_out_psum``) of the ``psum`` it closes, read off the compiled
programs when the trace was recorded."""
import gzip
import json

import pytest

from bench import model, spec, trace

FIX = spec.ROOT / "tests" / "bench" / "fixtures"
PSUMS = {"tp_attn_out_psum", "tp_ffn_out_psum"}


@pytest.fixture(scope="module")
def fx():
    with gzip.open(FIX / "v5e-granite8b-tp4-chat-trace.json.gz", "rt") as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def ctx(fx):
    red = trace.reduce(fx["events"], fx["records"])
    c = spec.load_json(spec.BENCH_DIR / "configs" / "granite-3.0-8b.json")
    for st in red["steps"]:
        st["flops"], st["bytes"] = model.step_work(c, st["pos"], st["chunks"])
    return {"steps": red["steps"], "window_s": red["window_s"],
            "busy_s": red["busy_s"], "chip_busy_s": red["chip_busy_s"],
            "chips": 4,
            "peaks": spec.load_json(spec.BENCH_DIR / "peaks.json")[
                "TPU v5 lite"]}


def test_recorded_four_chip_trace(fx, ctx):
    steps = ctx["steps"]
    assert [s["n"] for s in steps] == [r["n"] for r in fx["records"]]
    assert len(ctx["chip_busy_s"]) == 4
    for busy in ctx["chip_busy_s"]:
        assert 0 < busy < ctx["window_s"]
    for s in steps:
        assert 0 < s["collective_s"] < s["device_s"]
        assert 0 < s["pallas_s"] < s["device_s"]
        least = max(s["flops"] / (4 * 197e12), s["bytes"] / (4 * 819e9))
        assert 0 < least < s["device_s"]


def test_the_named_psums_count_as_collectives(fx):
    """Every all-reduce of the traced steps closes one of the two named
    output projections, and ``is_collective`` counts each of them."""
    named = {}
    for name, _s, d, _p in fx["events"]["ops"]:
        hlo = name.split(" = ")[0].lstrip("%")
        if hlo in fx["scopes"]:
            assert trace.is_collective(name)
            for sc in fx["scopes"][hlo]:
                named[sc] = named.get(sc, 0) + d
        elif trace.is_collective(name):
            pytest.fail(f"a collective under no named psum: {name}")
    assert set(named) == PSUMS and min(named.values()) > 0


# what the readers gave this trace when it was recorded
READ = {"collective_share": 17.977855693906324,
        "mesh_idle_share": 10.832771739274671,
        "mesh_step_mfu": 8.903290360270525,
        "mesh_step_roofline": 18.233219083923515}


@pytest.mark.parametrize("metric", ["collective_share", "mesh_idle_share",
                                    "mesh_step_mfu", "mesh_step_roofline"])
def test_mesh_readers_on_the_recorded_trace(ctx, metric):
    value = spec.load_metric_reader(metric)(ctx)
    assert 0 < value < 100
    assert value == pytest.approx(READ[metric], rel=1e-9)
