"""Host milliseconds in which the program's own ShardedLoader and
ContrastiveDataset make one global batch at the cell's sizes, alone on the
host (median of three batches, timed by the benchmark)."""


def read(run):
    return run.get("input_batch_ms")
