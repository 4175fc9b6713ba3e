"""The benchmark's copies of the pipelines give the benchmark's
references' answers through `Context` at a small size, and the control of
each comparison comes out as not correct."""

import pytest

from harness import datagen, spec

import json
import os

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _cells() -> list:
    """Every one-chip cell of BENCHMARK.json and of `bench/planned.json`
    with the size its traffic file gives for tests (`test_rows`): a later
    PR's cell is covered as it lands."""
    out = []
    for w in spec.entries(_ROOT)["workloads"]:
        if w["chips"] == 1:
            with open(os.path.join(_ROOT, "bench", "workloads",
                                   w["name"] + ".json")) as fp:
                out.append((w["name"], int(json.load(fp)["test_rows"])))
    return out


CELLS = _cells()


def _answers(name, rows, seed, work, pool, control=False):
    cell = spec.Cell(name)
    cell.scale_rows(rows)
    inp = datagen.generate(pool, cell, seed, work)
    datagen.start_reference(pool, cell, inp)
    want = datagen.merge_reference(cell, datagen.wait_reference(inp))
    got = None
    if control:
        datagen.start_reference(pool, cell, inp, control=True)
        got = datagen.merge_reference(cell, datagen.wait_reference(inp),
                                      control=True)
    return cell, inp, want, got


@pytest.mark.parametrize("name,rows", CELLS)
def test_pipeline_copy_matches_its_reference_through_context(
        name, rows, tmp_path, inline_pool):
    import tuplex_tpu

    cell, inp, want, _ = _answers(name, min(rows, 50000), 21,
                                  str(tmp_path / "w"), inline_pool)
    ctx = tuplex_tpu.Context(dict(cell.context_options))
    try:
        got = cell.pipeline().build(ctx, inp.paths).collect()
    finally:
        ctx.close()
    numbers = cell.pipeline().compare(got, want, cell.limits)
    assert all(v <= lim for _, v, lim in numbers), numbers
    assert cell.pipeline().answer_bytes(want) > 0


@pytest.mark.parametrize("name,rows", CELLS)
@pytest.mark.parametrize("seed", [31, 32, 33])
def test_the_control_is_not_correct(name, rows, seed, tmp_path, inline_pool):
    """The reference in the nearest lower precision (TPC-H: float32 sums)
    or with the order guarantee broken (Zillow) must fail a limit."""
    cell, _, want, control = _answers(name, rows, seed, str(tmp_path / "w"),
                                      inline_pool, control=True)
    numbers = cell.pipeline().compare(control, want, cell.limits)
    assert any(not v <= lim for _, v, lim in numbers), numbers
    same = cell.pipeline().compare(
        want if not isinstance(want, float) else [want], want, cell.limits)
    assert all(v <= lim for _, v, lim in same), same
