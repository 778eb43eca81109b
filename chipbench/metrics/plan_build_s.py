"""Host seconds from the benchmark's edge list to the program's placed plan:
``from_edges``, the Counter's first ``plan`` and the wait for its arrays."""


def read(run):
    return run["setup"]["plan_build_s"]
