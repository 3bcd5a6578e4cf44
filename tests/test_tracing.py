"""The program's own tracing: the continuous chunked serve loop's
``serve.*`` profiler spans and the names of its Pallas launches.

On the CPU: a tiny executed serve under ``jax.profiler.trace`` holds one
``serve.step`` per dispatched step with its host phases in order and their
args; a hook raising mid-step leaves every span of that step closed; an
iteration with nothing in flight records nothing; and every launch of the
program carries its members' names (the compiled form is checked in
``tests/test_tpu_compile.py``)."""
import dataclasses
import glob
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_config
from repro.core import hfuse
from repro.models import lm
from repro.serve.engine import PrefillBudget, Request, ServeEngine

ORDER = ("admit", "stage", "dispatch", "sync", "sample", "sync",
         "first_token")


@pytest.fixture(scope="module")
def engine():
    cfg = dataclasses.replace(get_config("granite-3-2b").reduced(),
                              dtype="float32")
    params = lm.init(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, batch=2, max_len=48,
                      scheduling="continuous", plan_fusion=True,
                      prefill_budget=PrefillBudget(chunk_rows=8,
                                                   max_coresident_chunks=2))
    assert eng.executed
    return eng


def _requests(cfg, lens=(6, 15, 11), new=(3, 4, 2), arrival=0):
    rng = np.random.default_rng(3)
    return [Request(rid=i, prompt=rng.integers(1, cfg.vocab_size, L)
                    .astype(np.int32), max_new_tokens=m, arrival=arrival)
            for i, (L, m) in enumerate(zip(lens, new))]


def _serve_spans(tmp_path, engine, requests):
    """Serve under the profiler, counting dispatches; the ``serve.*``
    spans as ``(name, start, end, args)`` sorted by start, and the
    dispatches as ``(step, chunks)``."""
    calls = []
    orig = engine._cb_step

    def counted(n):
        fn = orig(n)

        def call(*a, **kw):
            calls.append((engine.stats.steps, n))
            return fn(*a, **kw)
        return call

    engine._cb_step = counted
    err = None
    try:
        with jax.profiler.trace(str(tmp_path)):
            try:
                engine.run(requests)
            except RuntimeError as e:        # a hook's, raised mid-step
                err = e
    finally:
        del engine._cb_step
    path = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                     recursive=True)[-1]
    spans = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("serve."):
                    spans.append((e.name, e.start_ns,
                                  e.start_ns + e.duration_ns,
                                  dict(e.stats)))
    spans.sort(key=lambda s: (s[1], -s[2]))
    return spans, calls, err


def _by_step(spans):
    steps = []
    for name, s, e, args in spans:
        if name == "serve.step":
            steps.append({"span": (s, e, args), "children": []})
        else:
            assert steps and s >= steps[-1]["span"][0] \
                and e <= steps[-1]["span"][1], f"{name} outside its step"
            steps[-1]["children"].append((name[len("serve."):], s, e,
                                          args))
    return steps


def test_one_step_span_per_dispatch_with_phases_in_order(tmp_path, engine):
    spans, calls, err = _serve_spans(tmp_path, engine,
                                     _requests(engine.cfg))
    assert err is None and calls
    steps = _by_step(spans)
    assert [st["span"][2]["step_num"] for st in steps] == \
        [s for s, _ in calls]
    admitted = []
    for st, (step, n) in zip(steps, calls):
        kids = st["children"]
        names = [k[0] for k in kids]
        # in order, each once; the prompt logits' sync only with a chunk,
        # the first tokens only when a prompt completes
        done = "first_token" in names
        assert names == list(ORDER[:5 + bool(n) + done])
        assert n or not done
        for a, b in zip(kids, kids[1:]):
            assert a[2] <= b[1]                  # children do not overlap
        disp = dict((k[0], k[3]) for k in kids)["dispatch"]
        assert disp["step"] == step and disp["chunks"] == n
        assert disp["active"] == int(disp["active"])
        assert int(disp["shards"]) == engine.tp_shards == 1
        if "first_token" in names:
            admitted += str(dict((k[0], k[3]) for k in kids)
                            ["first_token"]["rids"]).split()
    assert sorted(admitted) == ["0", "1", "2"]


def test_hook_raising_mid_step_leaves_no_span_open(tmp_path, engine):
    """A hook that ends the serve from inside the sample loop (as the
    benchmark's window close does) still closes that step's spans: the
    trace holds them, each inside the step's span, the step's sample
    phase last."""
    n_tokens = []
    orig = engine._sample

    def sample(logits, req):
        n_tokens.append(1)
        if len(n_tokens) == 6:
            raise RuntimeError("window closed")
        return orig(logits, req)

    engine._sample = sample
    try:
        spans, calls, err = _serve_spans(tmp_path, engine,
                                         _requests(engine.cfg))
    finally:
        del engine._sample
    assert err is not None
    steps = _by_step(spans)
    assert len(steps) == len(calls)
    last = [k[0] for k in steps[-1]["children"]]
    assert last[-1] in ("sample", "first_token")
    assert "dispatch" in last and "sync" in last


def test_idle_iterations_record_no_span(tmp_path, engine):
    spans, calls, _ = _serve_spans(
        tmp_path, engine, _requests(engine.cfg, lens=(6,), new=(2,),
                                    arrival=5))
    steps = _by_step(spans)
    assert [st["span"][2]["step_num"] for st in steps] == \
        [s for s, _ in calls]
    assert steps[0]["span"][2]["step_num"] == 5


def test_launches_carry_their_members_names(engine):
    """Every launch of the executed program states its members: the
    ``name`` (``\\W`` read ``_``) and ``metadata={"launch": "a+b"}`` of
    its ``pallas_call``, the members ``Program.fused_members`` lists."""
    prog = engine.build_decode_program(prefill_chunks=1, interpret=True)
    assert prog.n_fused >= 1
    for step in prog.steps:
        args = [jax.ShapeDtypeStruct(o.shape, o.dtype)
                for op in step.ops for o in op.inputs]
        jaxpr = jax.make_jaxpr(step.call)(*args)
        eqns = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
        assert len(eqns) == 1
        launch = "+".join(step.members)
        assert dict(eqns[0].params["metadata"]) == {"launch": launch}
        name = eqns[0].params["name"]
        assert len(name) == len(launch) and name.isidentifier()
        assert name == "".join(c if c.isascii() and (c.isalnum()
                                                    or c == "_") else "_"
                               for c in launch)
    assert [s.members for s in prog.steps if s.fused] == prog.fused_members


def test_standalone_launch_is_named_after_its_op():
    from repro.kernels.rmsnorm import rmsnorm_op
    op = rmsnorm_op(8, 128, bm=8)
    run = hfuse.run_single(op, interpret=True)
    args = [jax.ShapeDtypeStruct(o.shape, o.dtype) for o in op.inputs]
    eqn = next(e for e in jax.make_jaxpr(run)(*args).eqns
               if e.primitive.name == "pallas_call")
    assert dict(eqn.params["metadata"]) == {"launch": op.name}
    assert eqn.params["name"] == op.name
