"""Per-layer readers that take their numbers from the loop's counters."""
import pytest

from bench import run, suite


def counters_run(counters: dict) -> run.Run:
    return run.Run(cell="c", device_kind="TPU v5 lite", cols=None,
                   shapes=None, counters=counters, trace=None)


@pytest.mark.parametrize("widths, want", [
    ([64] * 150, 100.0),                    # every launch full-width
    ([64, 64, 32, 64, 16, 32, 64, 64], 62.5),
    ([], None),                             # no launch: nothing to read
])
def test_serve_top_bucket_pct(widths, want):
    read = suite.reader("serve_top_bucket_pct")
    got = read(counters_run({"launched_widths": widths, "max_batch": 64}))
    assert got == (None if want is None else pytest.approx(want))


def test_serve_top_bucket_pct_needs_the_largest_bucket():
    read = suite.reader("serve_top_bucket_pct")
    assert read(counters_run({"launched_widths": [64, 64]})) is None

