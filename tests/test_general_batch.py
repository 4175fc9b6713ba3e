"""`runtime/columns.general_batch_size`: the padded rows of the general
tier's batch follow from the partition's own staged bucket and never from
how many rows deviated (PR 29; the job-level tests are in
`tests/test_mesh_zillow.py`)."""

import pytest


@pytest.mark.parametrize("k, part_rows, mode, want", [
    (1500, 111111, "q8", 3584),       # a Zillow partition: 114,688 / 32
    (3584, 111111, "q8", 3584),       # twice as dirty a file: the floor
    (6667, 111111, "q8", 7168),       # a 6% share: one doubling step
    (7169, 111111, "q8", 14336),
    (60000, 111111, "q8", 114688),    # capped at the partition's bucket
    (111111, 111111, "q8", 114688),
    (280, 20000, "q8", 640),
    (3, 40, "q8", 8),                 # tiny partitions: the floor is 8
    (37, 37, "q8", 40),
    (280, 20000, "pow2", 1024),
    (280, 20000, "exact", 280),       # exact mode pads nothing
], ids=lambda v: str(v))
def test_general_batch_size(k, part_rows, mode, want):
    from tuplex_tpu.runtime import columns as C

    got = C.general_batch_size(k, part_rows, mode)
    assert got == want
    assert got >= k
    # the steps a partition size can meet: the floor times a power of two
    floor = max(8, C.bucket_size(part_rows, mode) >> 5)
    assert mode == "exact" or got == C.bucket_size(part_rows, mode) \
        or (got % floor == 0 and (got // floor) & (got // floor - 1) == 0)
