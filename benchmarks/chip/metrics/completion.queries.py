"""completion.queries: simplices completed across segments per pass
(``EngineStats.completion_queries`` of each window pass's fresh engine). An
exact count; nothing to read in a cell that completes nothing."""


def read(run):
    q = run.per_pass("completion_queries")
    return q if q > 0 else None
