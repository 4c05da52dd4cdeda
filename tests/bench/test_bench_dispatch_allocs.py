"""`dispatch_allocs_per_step`: the runtime's output allocations inside the
trainer's dispatch of its step, on made-up intervals and on the traces
recorded on a TPU v5e."""
import pytest
from test_bench_host_spans import US, context, read, recorded

NAME = "dispatch_allocs_per_step"

# Dispatch spans [0, 10] and [20, 30] us.  Allocations at 0 (a span's
# start: counted), 5, 9, 25 (counted), 10 (a span's end), 15 and 35 (between
# and after the spans): 4 over 2 steps.
DISPATCH = [("train.dispatch", 0, 10 * US),
            ("train.dispatch", 20 * US, 10 * US)]
ALLOCS = [("AllocateRawBuffer", t * US, US)
          for t in (0, 5, 9, 10, 15, 25, 35)]


@pytest.mark.parametrize("host,value", [
    (DISPATCH + ALLOCS, 2.0),
    (DISPATCH, 0.0),                                   # every output donated
    (DISPATCH + [("MemoryAllocation", 5 * US, US)], 0.0),  # not counted
    (ALLOCS, None),                                    # no dispatch spans
])
def test_reader_on_made_up_intervals(host, value):
    assert read(NAME, context(host, steps=2)) == value


def test_recorded_run_allocates_the_whole_state_a_step(tmp_path_factory):
    """The program without donation: 640 allocations in 20 dispatches,
    params, θ_stale, store, step, rng and the step's metrics."""
    _, ctx = recorded(tmp_path_factory, "score_heavy_spans")
    assert read(NAME, ctx) == 32.0


def test_recorded_run_without_spans_gives_none(tmp_path_factory):
    _, ctx = recorded(tmp_path_factory, "score_heavy")
    assert read(NAME, ctx) is None
