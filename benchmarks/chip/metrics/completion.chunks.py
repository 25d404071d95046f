"""completion.chunks: completion chunks per pass, one per ``plan_completion``
call (``EngineStats.completion_chunks`` of each window pass's fresh
engine): how often the completion's per-chunk host round trips are paid.
Nothing to read in a cell that completes nothing, nor from a program
without the counter."""


def read(run):
    if not run.pass_stats or "completion_chunks" not in run.pass_stats[0]:
        return None
    q = run.per_pass("completion_chunks")
    return q if q > 0 else None
