"""The window rule, censored time to first token included."""
from types import SimpleNamespace

import pytest

from benchmarks.chip import window


def sent(due, stamps):
    return SimpleNamespace(due=due, stamps=list(stamps))


def test_tokens_ttft_and_gaps_inside_the_window():
    reqs = [sent(9.0, [9.5, 10.5, 11.0]),     # due before: no TTFT sample
            sent(10.0, [10.2, 10.4, 10.7]),
            sent(12.0, [12.5])]
    m = window.end_to_end(reqs, 10.0, 20.0)
    assert m["tokens"] == 6
    assert m["output_tok_s"] == pytest.approx(0.6)
    assert m["n_due"] == 2
    assert m["n_gaps"] == 4          # 10.5 and 11.0, 10.4 and 10.7
    assert m["ttft_p95_ms"] == pytest.approx(
        1e3 * (0.2 + 0.95 * (0.5 - 0.2)))


def test_a_request_without_a_first_token_counts_to_the_close():
    reqs = [sent(10.0, [10.1, 10.2]), sent(11.0, []), sent(12.0, [25.0])]
    m = window.end_to_end(reqs, 10.0, 20.0)
    # TTFTs: 0.1, 9.0 (censored), 8.0 (first token after the close)
    assert m["n_due"] == 3
    assert m["ttft_p95_ms"] == pytest.approx(1e3 * (8.0 + 0.9 * 1.0))


def test_gap_counts_when_its_later_token_is_inside():
    m = window.end_to_end([sent(5.0, [9.0, 10.5]), sent(11.0, [11.1])],
                          10.0, 20.0)
    assert m["n_gaps"] == 1 and m["itl_p95_ms"] == pytest.approx(1500.0)


def test_an_empty_window_is_an_error():
    with pytest.raises(RuntimeError):
        window.end_to_end([sent(1.0, [1.5])], 10.0, 20.0)
