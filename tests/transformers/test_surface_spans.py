"""The DataFrame surface's two spans (ISSUE 24): ``surface.extract`` around
row extraction and ``surface.run`` around the runner, the pair PERF.md
section 7 row 0's finding (every row extracted before the first dispatch)
is to be read from."""

import jax.numpy as jnp
import numpy as np
import pytest

from sparkdl_tpu.observability import tracing
from sparkdl_tpu.transformers._inference import (
    BatchedRunner,
    run_partition_with_passthrough,
)


def _extract(row):
    if row["x"] is None:
        raise ValueError("undecodable row")
    return {"x": np.full((4,), row["x"], np.float32)}


def _rows(n):
    return [{"id": i, "x": None if i == 2 else float(i)} for i in range(n)]


@pytest.mark.parametrize("n_rows", [1, 7])
def test_extract_and_run_both_appear_and_do_not_overlap(n_rows):
    runner = BatchedRunner(lambda b: b["x"] * 2.0, batch_size=4,
                           data_parallel=False)
    tracing.clear_trace()
    tracing.enable_tracing()
    try:
        with tracing.span("caller") as caller:
            out = list(run_partition_with_passthrough(
                _rows(n_rows), _extract, runner, "y"))
            # the generator's spans never leak into its consumer
            assert tracing.current_context() == caller.context
        events = {e["name"]: e for e in tracing.trace_events()
                  if e["name"].startswith("surface.")}
    finally:
        tracing.disable_tracing()
        tracing.clear_trace()
    assert [r["id"] for r in out] == list(range(n_rows))
    valid = n_rows - (1 if n_rows > 2 else 0)
    assert sum(r["y"] is not None for r in out) == valid
    assert set(events) == {"surface.extract", "surface.run"}
    extract, run = events["surface.extract"], events["surface.run"]
    assert extract["args"]["rows"] == n_rows and run["args"]["rows"] == valid
    assert extract["ts"] + extract["dur"] <= run["ts"]
    for e in (extract, run):
        assert e["args"]["parent_id"] == caller.context.span_id


def test_no_span_with_tracing_off():
    runner = BatchedRunner(lambda b: jnp.tanh(b["x"]), batch_size=4,
                           data_parallel=False)
    tracing.disable_tracing()
    tracing.clear_trace()
    assert len(list(run_partition_with_passthrough(
        _rows(3), _extract, runner, "y"))) == 3
    assert tracing.trace_events() == []
