"""The control: the reference computed in int8, put in the program's place
at the same prompts and served tokens, must come out as not correct, while
the program on the same run comes out correct (test size, CPU)."""
import pytest

from test_bench_harness import TINY_LIMIT, _run


@pytest.mark.parametrize("seed", [2 ** 32 + 1, 2 ** 32 + 2, 2 ** 32 + 3])
def test_int8_control_fails_where_the_program_passes(seed):
    res = _run(seed=seed, control=1)
    c = res["compared"]
    assert res["correct"] is True
    assert c["logit_gap_mean"]["value"] <= TINY_LIMIT
    assert c["control_logit_gap_mean"]["value"] > TINY_LIMIT
