"""Inputs and reference answers, made from `--seed` in helper processes.

The helpers are spawned (never forked from the process that holds the
chip) and pinned to `JAX_PLATFORMS=cpu` before they import anything. A
table is cut into chunks of a fixed number of rows; chunk `i` is made from
`random.Random("<seed>:<table>:<i>")`, so the bytes of a table depend on the
seed alone, whatever the number of helpers. The reference walks the same
chunk files with the `csv` module and leaves one pickled partial answer a
chunk, which the harness merges once the window has closed.
"""

from __future__ import annotations

import csv
import io
import os
import pickle
import random
import shutil
import sys

from harness import spec

_SIDE_CACHE: dict = {}


def helper_init(bench_dir: str) -> None:
    os.environ["JAX_PLATFORMS"] = "cpu"
    if bench_dir not in sys.path:
        sys.path.insert(0, bench_dir)


def pool(bench_dir: str, workers: int | None = None):
    import multiprocessing as mp
    from concurrent.futures import ProcessPoolExecutor

    if workers is None:
        workers = max(2, min(12, (os.cpu_count() or 2) - 1))
    return ProcessPoolExecutor(
        max_workers=workers, mp_context=mp.get_context("spawn"),
        initializer=helper_init, initargs=(bench_dir,))


def chunk_plan(rows: int, chunk_rows: int) -> list:
    """[(index, first_row, n_rows)] covering `rows`."""
    return [(i, first, min(chunk_rows, rows - first))
            for i, first in enumerate(range(0, rows, chunk_rows))]


def chunk_rng(seed: int, table: str, index: int) -> random.Random:
    return random.Random(f"{int(seed)}:{table}:{int(index)}")


def _generator(config_dir: str):
    return spec.config_module(config_dir, "generate")


def gen_task(config_dir: str, table: str, index: int, first_row: int,
             n_rows: int, seed: int, params: dict, out_path: str) -> tuple:
    gen = _generator(config_dir)
    rows = gen.gen_chunk(table, chunk_rng(seed, table, index), n_rows,
                         first_row, params)
    if len(rows) != n_rows:
        raise RuntimeError(f"{table} chunk {index}: {len(rows)} rows made, "
                           f"{n_rows} asked")
    with open(out_path, "w", newline="") as fp:
        csv.writer(fp).writerows(rows)
    return n_rows, os.path.getsize(out_path)


def _read_rows(path: str, header: bool) -> list:
    with open(path, newline="") as fp:
        r = csv.reader(fp)
        if header:
            next(r)
        return list(r)


def _side_tables(config_dir: str, side_paths: dict) -> dict:
    gen = _generator(config_dir)
    side = {}
    for table, path in side_paths.items():
        key = (path, os.path.getmtime(path), os.path.getsize(path))
        if key not in _SIDE_CACHE:
            _SIDE_CACHE.clear()
            _SIDE_CACHE[key] = {"columns": gen.COLUMNS[table],
                                "rows": _read_rows(path, header=True)}
        side[table] = _SIDE_CACHE[key]
    return side


def ref_task(config_dir: str, pipeline: str, table: str, chunk_path: str,
             side_paths: dict, out_path: str, control: bool) -> str:
    gen = _generator(config_dir)
    pipe = spec.config_module(config_dir, pipeline)
    partial = pipe.reference_partial(
        gen.COLUMNS[table], _read_rows(chunk_path, header=False),
        _side_tables(config_dir, side_paths), control)
    with open(out_path, "wb") as fp:
        pickle.dump(partial, fp, protocol=pickle.HIGHEST_PROTOCOL)
    return out_path


class Inputs:
    """The tables of one run on disk and the reference's partial answers."""

    def __init__(self, work: str):
        self.work = work
        self.paths: dict = {}       # table -> csv with header
        self.rows: dict = {}
        self.bytes: dict = {}
        self.ref_futures: list = []
        self._chunks: dict = {}     # table -> [chunk path]

    def input_bytes(self, tables) -> int:
        return sum(self.bytes[t] for t in tables)


def generate(helpers, cell, seed: int, work: str) -> Inputs:
    """Make every table the cell's pipeline reads. Blocks until the files
    are whole; the chunk files stay for the reference."""
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    gen = cell.generator()
    inp = Inputs(work)
    futs = {}
    for table in cell.job_tables():
        t = cell.tables[table]
        for index, first, n in chunk_plan(int(t["rows"]),
                                          int(t["chunk_rows"])):
            path = os.path.join(work, f"{table}.{index:05d}.chunk")
            futs[(table, index)] = helpers.submit(
                gen_task, cell.config_dir, table, index, first, n, seed,
                cell.params, path)
            inp._chunks.setdefault(table, []).append(path)
    for table in cell.job_tables():
        out = os.path.join(work, table + ".csv")
        header = io.StringIO(newline="")
        csv.writer(header).writerow(gen.COLUMNS[table])
        rows = 0
        with open(out, "wb") as raw:
            raw.write(header.getvalue().encode())
            for index, path in enumerate(inp._chunks[table]):
                n, _ = futs[(table, index)].result()
                rows += n
                with open(path, "rb") as src:
                    shutil.copyfileobj(src, raw, 1 << 22)
        inp.paths[table] = out
        inp.rows[table] = rows
        inp.bytes[table] = os.path.getsize(out)
    return inp


def start_reference(helpers, cell, inp: Inputs, control: bool = False) -> None:
    pipe = cell.pipeline()
    side_paths = {t: inp.paths[t] for t in pipe.SIDE}
    tag = "control" if control else "ref"
    inp.ref_futures = [
        helpers.submit(ref_task, cell.config_dir, cell.pipeline_name,
                       pipe.FACT, path, side_paths,
                       os.path.join(inp.work, f"{tag}.{i:05d}.pkl"), control)
        for i, path in enumerate(inp._chunks[pipe.FACT])]


def wait_reference(inp: Inputs) -> list:
    """Paths of the partial answers, in chunk order, once all are written."""
    return [f.result() for f in inp.ref_futures]


def merge_reference(cell, partial_paths: list, control: bool = False):
    partials = []
    for p in partial_paths:
        with open(p, "rb") as fp:
            partials.append(pickle.load(fp))   # written by our own helpers
    return cell.pipeline().reference_merge(partials, control)
