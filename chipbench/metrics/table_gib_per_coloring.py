"""GiB that one more coloring of a batch adds to the compiled counting
program, by ``memory_analysis()``: the batch-2 program's need less the
batch-1 program's, from the compiles that size the batch."""


def read(run):
    per = run["memory"]["per_coloring_bytes"]
    return None if per is None else per / 2**30
