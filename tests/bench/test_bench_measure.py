"""Window arithmetic: percentiles over all requests, censoring at close."""
from types import SimpleNamespace as NS

import numpy as np
import pytest

from bench import measure


def _req(due, times):
    return NS(due=due, token_times=list(times))


def test_ttft_counts_every_request_due_in_window_and_censors():
    reqs = [_req(0.5, [0.7]),          # due before the window: left out
            _req(1.0, [1.2, 1.3]),     # 0.2
            _req(2.0, [2.5]),          # 0.5
            _req(3.0, []),             # no token by close 10: waits 7.0
            _req(4.0, [10.5]),         # first token after close: 6.0
            _req(10.0, [10.1])]        # due at close: left out
    s = sorted(measure.ttft_samples(reqs, 1.0, 10.0))
    assert s == pytest.approx([0.2, 0.5, 6.0, 7.0])
    v = measure.end_to_end("ttft_p50_ms", reqs, 1.0, 10.0, 0.0)
    assert v == pytest.approx(1e3 * np.percentile([0.2, 0.5, 6.0, 7.0], 50))
    v90 = measure.end_to_end("ttft_p90_ms", reqs, 1.0, 10.0, 0.0)
    assert v90 == pytest.approx(1e3 * np.percentile([0.2, 0.5, 6, 7], 90))


def test_gaps_are_every_gap_whose_later_token_is_in_window():
    reqs = [_req(0.0, [0.5, 1.5, 1.6, 9.9, 10.2]),
            _req(2.0, [2.1, 2.4])]
    g = sorted(measure.gap_samples(reqs, 1.0, 10.0))
    assert g == pytest.approx([0.1, 0.3, 1.0, 8.3])
    assert measure.end_to_end("itl_p95_ms", reqs, 1.0, 10.0, 0.0) == \
        pytest.approx(1e3 * np.percentile([0.1, 0.3, 1.0, 8.3], 95))


def test_tokens_per_s_and_setup():
    reqs = [_req(0.0, [0.5, 1.5, 2.5, 10.0]), _req(0.0, [3.0])]
    assert measure.end_to_end("tokens_per_s", reqs, 1.0, 10.0, 0.0) == \
        pytest.approx(3 / 9.0)
    assert measure.end_to_end("setup_s", reqs, 1.0, 10.0, 42.5) == 42.5
    with pytest.raises(KeyError):
        measure.end_to_end("nothing", reqs, 1.0, 10.0, 0.0)
