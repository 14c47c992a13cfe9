"""A planned recompute must find its producer's inputs on the device.

The recompute of a tensor runs after the releases of its trigger op, the
op before the one that needs the tensor.  An input of the producer last
used by that op is gone by then; the executor would regenerate it from
its own producer, and so on up the graph to a step input that nothing
can regenerate (``KeyError: ... has no producer``)."""
import numpy as np
import pytest

from repro.core import JaxprExecutor, RecomputePlanner, SchedulingPlan
from repro.core.executor import reference_outputs
from repro.core.recompute_planner import RecomputeCandidate

from helpers import capture_mlp


@pytest.fixture(scope="module")
def mlp():
    return capture_mlp(sizes=(64, 128, 128, 8), batch=16)


def _accepted(seq):
    """Every (tensor, previous access, target) the planner would accept."""
    planner = RecomputePlanner(seq, SchedulingPlan(job_id=seq.job_id))
    out = []
    for tid, spec, tga, tuas, rec_time in planner._eligible():
        cursor = tga
        for a in tuas:
            if planner._inputs_resident_at(tga.op_idx, a.op_idx, set()):
                out.append(RecomputeCandidate(
                    tensor_id=tid, job_id=seq.job_id,
                    size_bytes=spec.size_bytes, recompute_time=rec_time,
                    release_after_op=cursor.op_idx, target_op=a.op_idx,
                    producer_op=tga.op_idx))
            cursor = a
    return out


def test_no_accepted_input_is_freed_before_the_recompute(mlp):
    seq, _, _ = mlp
    cands = _accepted(seq)
    assert cands
    for c in cands:
        for tid in seq.operators[c.producer_op].inputs:
            spec = seq.tensors.get(tid)
            if spec is None or spec.kind.name in ("PARAM", "OPT_STATE"):
                continue
            # used again at or after the target: not freed by the trigger
            assert seq.last_access(tid).op_idx >= c.target_op, (c, tid)


def test_every_accepted_recompute_runs_once_and_matches(mlp):
    """Each recompute the planner may plan, alone in a plan, runs as one
    recompute in the executor and leaves the step's outputs as they are."""
    seq, closed, args = mlp
    want = [np.asarray(x) for x in reference_outputs(closed, *args)]
    cands = _accepted(seq)
    # the candidates whose target follows their producer directly are
    # the ones a time comparison let through with an input already freed
    assert any(c.target_op == c.producer_op + 1 for c in cands)
    for c in cands:
        plan = SchedulingPlan(job_id=seq.job_id)
        RecomputePlanner(seq, plan).apply(c)
        ex = JaxprExecutor(closed, seq, plan)
        got = ex.run(*args)
        assert ex.stats.recompute_count == 1, c
        for g, w in zip(got, want):
            np.testing.assert_allclose(np.asarray(g), w, rtol=1e-6,
                                       atol=1e-6)
