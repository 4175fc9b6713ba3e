"""Stage exec: partitions of the window that ran a second time, without
filter compaction, because the survivors of a filter overflowed the bucket
the sample had sized (the stage records' `compaction_reruns`). 0 is the
expected reading; None on a program whose records lack the counter."""

from harness import reading


def read(run: dict):
    n = reading.stage_sum(run["window"]["stages"], "compaction_reruns")
    return None if n is None else int(n)
