"""The plain reference, and the comparison that decides ``correct``.

The reference is the configuration's architecture in float32 ``jax.numpy``
(``forward`` of ``bench/archs/<model_type>.py``), written from the
configuration file and importing nothing of the program.  It reads the
weights the benchmark drew, in the program's parameter layout, where they
lie: on a mesh, ``jit`` partitions it over the cell's chips.

The comparison: teacher-force each sampled request's prompt and served
tokens through the reference and read, at every served token, how far its
logit lies below the reference's best (0 where the reference agrees).
The mean of these gaps over the sample is compared with the cell's limit;
the widest gap is reported beside it.  The control computes the same
forward with int8 weights and activations (per-column and per-row scales)
and reads the gaps of the tokens that it puts first.
"""
from __future__ import annotations

import numpy as np

from bench.model import arch


def make_reference(c: dict):
    """Jitted (gaps, control_top) functions for configuration ``c``.

    ``gaps(params, tokens, want)``: for ``want`` (K, T) token ids, the
    float32 reference's best logit minus its logit of ``want[k, t]`` at
    every position t.  ``control_top(params, tokens)``: the token that
    the int8 control puts first at every position.
    """
    import jax
    import jax.numpy as jnp

    forward = arch(c).forward

    @jax.jit
    def gaps(params, tokens, want):
        logits = forward(c, params, tokens, int8=False)
        best = jnp.max(logits, axis=-1)
        got = jnp.take_along_axis(logits, want.T, axis=-1).T   # (K, T)
        return best[None, :] - got

    @jax.jit
    def control_top(params, tokens):
        return jnp.argmax(forward(c, params, tokens, int8=True), axis=-1)

    return gaps, control_top


def pick_sample(requests, seed: int, min_tokens: int,
                max_requests: int) -> list:
    """Finished requests drawn from ``seed``: the one with the most served
    tokens, then others in random order until ``min_tokens`` served tokens
    or ``max_requests`` requests.  Requests still in flight stand in only
    where none finished."""
    pool = [r for r in requests if r.done and r.out_tokens]
    if not pool:
        pool = [r for r in requests if r.out_tokens]
    if not pool:
        return []
    longest = max(pool, key=lambda r: (len(r.out_tokens), -r.rid))
    rest = [r for r in pool if r is not longest]
    rng = np.random.default_rng(seed % 2 ** 64)
    rng.shuffle(rest)
    picked, n = [longest], len(longest.out_tokens)
    for r in rest:
        if n >= min_tokens or len(picked) >= max_requests:
            break
        picked.append(r)
        n += len(r.out_tokens)
    return picked


def compare(c: dict, params, sample, rows: int,
            control: bool = False) -> dict:
    """Mean and widest gap of the served tokens of ``sample`` (and of the
    control's first choices) below the reference's best logit.

    Each request is teacher-forced as prompt + served tokens, padded to
    ``rows`` tokens (causal attention leaves the real positions unchanged).
    """
    import jax.numpy as jnp

    gaps_fn, top_fn = make_reference(c)
    served, control_g, agree = [], [], 0
    for r in sample:
        out = [int(t) for t in r.out_tokens]
        seq = np.concatenate([np.asarray(r.prompt, np.int32),
                              np.asarray(out[:-1], np.int32)])
        P, n = len(r.prompt), len(out)
        if len(seq) > rows:
            raise ValueError(f"request {r.rid}: {len(seq)} tokens exceed "
                             f"the reference's {rows} rows")
        toks = np.zeros(rows, np.int32)
        toks[:len(seq)] = seq
        at = np.arange(P - 1, P - 1 + n)
        want = np.zeros((2, rows), np.int32)
        want[0, at] = out
        want[1, at] = out
        if control:
            top = np.asarray(top_fn(params, jnp.asarray(toks)))
            want[1, at] = top[at]
        g = np.asarray(gaps_fn(params, jnp.asarray(toks), jnp.asarray(want)))
        served.append(g[0, at])
        agree += int((g[0, at] == 0).sum())
        if control:
            control_g.append(g[1, at])
    served = np.concatenate(served) if served else np.zeros(0)
    res = {"logit_gap_mean": float(served.mean()) if served.size else 0.0,
           "logit_gap_max": float(served.max()) if served.size else 0.0,
           "positions": int(served.size), "requests": len(sample),
           "agree_top1": agree}
    if control:
        cg = np.concatenate(control_g)
        res["control_logit_gap_mean"] = float(cg.mean())
        res["control_logit_gap_max"] = float(cg.max())
    return res
