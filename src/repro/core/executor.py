"""Plan → program: execute a FusionPlan on live arrays.

The missing half of the paper's pipeline.  HFuse doesn't stop at a schedule
table — it emits fused source that *replaces* the original kernel launches.
``compile_plan`` is that step for this repro: it lowers a
``planner.FusionPlan`` over a ``GraphOp`` graph into a ``Program`` — a pure,
jit-compatible ``state -> state`` function in which

  * every fused bundle runs as the single Pallas call built by
    ``FusionDecision.result.build()`` (the tuned schedule, the tuned
    block-shrink variant, the tuned VMEM cap),
  * every leftover (unfused) op runs via ``hfuse.run_single``,
  * operands are threaded through a ``binding.BindingRegistry`` — the graph
    names stay symbolic here; the registry owns the mapping onto live
    param/grad/opt-state leaves (train) or KV-cache blocks and activations
    (serve).

Ordering: bundles are contracted to super-nodes (the planner only fuses
mutually independent ops, so a bundle is internally unordered) and the
contracted DAG is topologically sorted.  A dependency cycle *between*
bundles — two bundles each containing an op that feeds the other — can no
longer be planned: ``planner._contracted_acyclic`` rejects any candidate
grouping that would contract into a cycle.  The toposort here stays the
backstop for hand-built plans, surfacing the cycle as an error instead of
silently misexecuting.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Optional, Sequence

from repro.core import hfuse
from repro.core.binding import BindingRegistry, State
from repro.core.op_spec import OpSpec
from repro.core.planner import FusionPlan, GraphOp
from repro.distributed import hlo_analysis


@dataclass
class ProgramStep:
    """One launch of the compiled program."""
    members: tuple[str, ...]
    call: Callable                      # fused bundle or single-op pallas call
    ops: tuple[OpSpec, ...]             # execution OpSpecs (tuned variant)
    fused: bool
    schedule: Optional[str] = None      # ratio label, fused steps only

    def describe(self) -> dict:
        return {"members": "+".join(self.members),
                "kind": "fused" if self.fused else "single",
                "schedule": self.schedule}


@dataclass(eq=False)                       # identity hash: jax.jit(program)
class Program:
    """Executable lowering of a FusionPlan.  ``program(state) -> state`` is
    pure and traceable — wrap it (or the step function that embeds it) in
    ``jax.jit``."""
    steps: list[ProgramStep]
    bindings: BindingRegistry
    graph: tuple[GraphOp, ...]
    interpret: bool = False             # kernels run in the Pallas interpreter

    def __call__(self, state: State) -> State:
        for step in self.steps:
            args = [a for op in step.ops
                    for a in self.bindings.inputs(op, state)]
            outs = step.call(*args)
            off = 0
            for op in step.ops:
                n = len(op.outputs)
                state = self.bindings.commit(op, state, outs[off:off + n])
                off += n
        return state

    def describe(self) -> list[dict]:
        return [s.describe() for s in self.steps]

    @property
    def n_fused(self) -> int:
        return sum(1 for s in self.steps if s.fused)

    @property
    def fused_members(self) -> list[tuple[str, ...]]:
        """Member names of each fused launch — the co-residency record
        (e.g. the serve engine checks a prefill chunk actually shares a
        launch with decode attention before counting a step as fused-mixed)."""
        return [s.members for s in self.steps if s.fused]


def _toposort(nodes: dict[int, set[int]], order: Sequence[int]) -> list[int]:
    """Kahn's algorithm, stable in the given node order."""
    indeg = {n: len(d) for n, d in nodes.items()}
    users: dict[int, list[int]] = {n: [] for n in nodes}
    for n, deps in nodes.items():
        for d in deps:
            users[d].append(n)
    ready = [n for n in order if indeg[n] == 0]
    out: list[int] = []
    while ready:
        n = ready.pop(0)
        out.append(n)
        for u in users[n]:
            indeg[u] -= 1
            if indeg[u] == 0:
                ready.append(u)
    if len(out) != len(nodes):
        stuck = sorted(set(nodes) - set(out))
        raise ValueError(
            f"fusion plan is not executable: dependency cycle through "
            f"bundle nodes {stuck} (two bundles feed each other)")
    return out


def compile_plan(plan: FusionPlan, graph: Optional[Sequence[GraphOp]] = None,
                 bindings: Optional[BindingRegistry] = None, *,
                 interpret: bool = False) -> Program:
    """Lower ``plan`` over ``graph`` into an executable Program.

    ``graph`` defaults to the graph the plan was built from
    (``FusionPlan.graph``, recorded by ``planner.plan``).  ``bindings``
    must cover every named operand of every graph op; pass
    ``binding.default_bindings(ops)`` for the synthesized-state form.
    """
    if not interpret:
        # the plan priced its bundles with the v5e peaks: compiling it for
        # a TPU kind with no peak row is refused rather than mis-planned
        hlo_analysis.chip()
    graph = tuple(graph if graph is not None else (plan.graph or ()))
    if not graph:
        raise ValueError("compile_plan needs the planner graph "
                         "(plan.graph is empty and none was passed)")
    by_name = {g.op.name: g for g in graph}

    # ---- contract fused bundles into super-nodes -------------------------
    node_members: list[tuple[str, ...]] = \
        [d.members for d in plan.fused] + [(s,) for s in plan.singles]
    covered = [m for ms in node_members for m in ms]
    if sorted(covered) != sorted(by_name):
        raise ValueError(
            f"plan does not cover the graph exactly: plan={sorted(covered)} "
            f"graph={sorted(by_name)}")
    node_of = {m: i for i, ms in enumerate(node_members) for m in ms}
    deps: dict[int, set[int]] = {i: set() for i in range(len(node_members))}
    for i, ms in enumerate(node_members):
        for m in ms:
            for d in by_name[m].deps:
                if d in node_of and node_of[d] != i:
                    deps[i].add(node_of[d])

    order = _toposort(deps, range(len(node_members)))

    # ---- lower each node -------------------------------------------------
    if bindings is None:
        from repro.core.binding import default_bindings
        bindings = default_bindings([g.op for g in graph])
    decisions = {d.members: d for d in plan.fused}
    steps: list[ProgramStep] = []
    for i in order:
        members = node_members[i]
        if members in decisions:
            res = decisions[members].result
            call = res.build(interpret=interpret)
            ops = res.ops                       # tuned (possibly shrunk) variant
            steps.append(ProgramStep(members, call, tuple(ops), True,
                                     res.best.sched.label()))
        else:
            op = by_name[members[0]].op
            call = hfuse.run_single(op, interpret=interpret)
            steps.append(ProgramStep(members, call, (op,), False))
        for op in steps[-1].ops:
            bindings.validate(op)
    return Program(steps=steps, bindings=bindings, graph=graph,
                   interpret=interpret)
