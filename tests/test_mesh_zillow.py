"""Zillow Z1 through `Context` on the mesh backend (four of the eight
virtual devices) against the plain CPython reference of the benchmark's
`zillow-z1-host4` configuration: rows exact and in order on seeded data
with 6% deviant rows, the general tier's batch shaped by the partition and
not by the rows that deviated (a second file of the same distribution
compiles nothing), and the spans of the mesh path. The cases of the
batch rule itself are in `tests/test_general_batch.py`: xdist's `loadfile`
hands files out by their number of tests, and a file of more than eleven
would move `tests/test_faults.py` in that order (CHANGES.md, PR 29)."""

import csv
import importlib.util
import os
import random
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG_DIR = os.path.join(REPO, "bench", "configs", "zillow-z1-host4")
ROWS = 20000
PARAMS = {"dirty_facts": 0.04, "dirty_postal": 0.02}
MESH = {"tuplex.backend": "multihost", "tuplex.tpu.meshShape": "4"}


def _load(stem):
    """One file of the configuration by path, registered in `sys.modules`
    so that `inspect.getsource` finds the UDFs (as
    `bench/harness/spec.py::load_module` does)."""
    name = "test_mesh_zillow_" + stem
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(CONFIG_DIR, stem + ".py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


GEN, Z1 = _load("generate"), _load("z1")


def _listings(tmp_path, seed):
    """(csv path, the reference's answer) of one seeded file."""
    rows = GEN.gen_chunk("listings", random.Random(f"{seed}:listings:0"),
                         ROWS, 0, PARAMS)
    path = str(tmp_path / f"listings_{seed}.csv")
    with open(path, "w", newline="") as fp:
        w = csv.writer(fp)
        w.writerow(GEN.COLUMNS["listings"])
        w.writerows(rows)
    want = Z1.reference_merge(
        [Z1.reference_partial(GEN.COLUMNS["listings"], rows, {})])
    return path, want


@pytest.fixture(scope="module", autouse=True)
def _leave_no_exception_profile():
    """The deviant rows of this file's jobs are no later file's drift:
    `exception_drift` is a health check of the whole process."""
    yield
    from tuplex_tpu.runtime import excprof

    excprof.clear()


@pytest.fixture(autouse=True)
def _no_fork_compiles(monkeypatch):
    monkeypatch.setenv("TUPLEX_COMPILE_ISOLATION", "thread")


@pytest.fixture()
def mesh_ctx():
    import tuplex_tpu

    ctx = tuplex_tpu.Context(dict(MESH))
    yield ctx
    ctx.close()


@pytest.fixture()
def trace_on():
    from tuplex_tpu.runtime import tracing

    tracing.enable(True)
    tracing.clear()
    yield tracing
    tracing.enable(False)
    tracing.clear()


def _job(ctx, path):
    n0 = len(ctx.metrics.stages)
    out = Z1.build(ctx, {"listings": path}).collect()
    return out, ctx.metrics.stages[n0:]


def _general_specs(backend):
    """The batch specs the general tier's executable was called with."""
    return {spec for key, specs in backend.jit_cache._traced.items()
            if str(key[1]).startswith("general/") for spec in specs}


def _deviant(seed):
    rows = GEN.gen_chunk("listings", random.Random(f"{seed}:listings:0"),
                         ROWS, 0, PARAMS)
    return sum(1 for r in rows if r[6] in GEN._BROKEN_FACTS
               or r[4] in GEN._BROKEN_POSTAL)


def test_the_seeded_file_has_six_percent_deviant_rows():
    for seed in (11, 13):
        assert 0.05 * ROWS < _deviant(seed) < 0.07 * ROWS


def test_z1_on_the_mesh_equals_the_reference_row_for_row(tmp_path, mesh_ctx):
    from tuplex_tpu.exec.multihost import MultiHostBackend

    path, want = _listings(tmp_path, 11)
    got, stages = _job(mesh_ctx, path)
    assert type(mesh_ctx.backend) is MultiHostBackend
    assert mesh_ctx.backend.n_devices == 4
    assert len(want) > 1000
    assert Z1.compare(got, want, {}) == [("rows_missing_or_extra", 0, 0),
                                         ("rows_differ", 0, 0)]
    assert [tuple(r) for r in got[:50]] == want[:50]        # in order
    assert stages and all(m.get("tier") == "compiled" for m in stages)
    assert not mesh_ctx.backend.failure_log
    # the deviant rows went through the general tier, on the mesh
    assert sum(m.get("general_path_s", 0.0) for m in stages) > 0
    lay = mesh_ctx.backend.shard_layout
    assert len(lay["input"]) == 4
    assert len({shape for _, shape in lay["input"]}) == 1   # even shards


def test_a_second_seed_compiles_nothing_and_meets_the_same_batch(
        tmp_path, mesh_ctx):
    """Two files of one distribution in one process: the rows that deviate
    differ in number (261 and 242 reach the general tier: two of q8's
    steps, 288 and 256), the general tier's batch does not."""
    from tuplex_tpu.exec import compilequeue as CQ

    p1, want1 = _listings(tmp_path, 11)
    p2, want2 = _listings(tmp_path, 13)
    assert want1 != want2
    got1, _ = _job(mesh_ctx, p1)
    specs1 = _general_specs(mesh_ctx.backend)
    cq0 = CQ.snapshot()
    got2, stages2 = _job(mesh_ctx, p2)
    d = CQ.delta(cq0)
    assert d["stage_compiles"] == 0 and d["aot_misses"] == 0, d
    assert specs1 and _general_specs(mesh_ctx.backend) == specs1
    assert all(m.get("tier") == "compiled" for m in stages2)
    for got, want in ((got1, want1), (got2, want2)):
        assert Z1.compare(got, want, {}) == [
            ("rows_missing_or_extra", 0, 0), ("rows_differ", 0, 0)]


@pytest.mark.parametrize("k", [1402, 1572], ids=str)
def test_a_zillow_partition_meets_one_batch_whatever_the_seed(k):
    """What reaches the tier of a 111,111-row partition on the chip, at
    both ends of twelve seeds: 114,688 / 32."""
    from tuplex_tpu.runtime import columns as C

    assert C.general_batch_size(k, 111111, "q8") == 3584
    assert len({C.bucket_size(k, "q8") for k in (1402, 1500, 1572)}) == 3


def test_the_mesh_spans_open_with_their_attributes(tmp_path, mesh_ctx,
                                                   trace_on):
    path, _ = _listings(tmp_path, 11)
    _job(mesh_ctx, path)
    t0 = trace_on.now_us()
    _job(mesh_ctx, path)                      # warm: no first call left
    evs = [e for e in trace_on.events_since(t0) if e.get("dur") is not None]
    by = {}
    for e in evs:
        by.setdefault(e["name"], []).append(e)
    ids = {e["id"]: e for e in evs}

    puts = by["h2d:mesh-put"]
    assert all(e["cat"] == "xfer" for e in puts)
    assert all(e["args"]["devices"] == 4 and e["args"]["leaves"] > 10
               and e["args"]["bytes"] > 0 for e in puts)
    parents = {ids[e["parent"]]["name"] for e in puts}
    assert parents == {"dispatch:launch", "resolve:general"}

    (gen,) = by["resolve:general"]            # one partition at this size
    a = gen["args"]
    assert a["path"] == "mesh" and a["first_call"] == 0
    assert a["batch"] == 640 and a["rows"] == 261
    # the fast path's batch and the general tier's, each placed once
    stage_put = [e for e in puts
                 if ids[e["parent"]]["name"] == "dispatch:launch"]
    gen_put = [e for e in puts if e["parent"] == gen["id"]]
    assert len(gen_put) == 1 and len(stage_put) == 1
    assert gen_put[0]["args"]["bytes"] < stage_put[0]["args"]["bytes"] / 8

    fetches = by["d2h:leaf-fetch"]
    assert fetches and all(e["args"]["devices"] == 4
                           and e["args"]["shards"] >= 4
                           and e["args"]["bytes"] > 0 for e in fetches)


@pytest.mark.parametrize("host_rows, want_path", [
    (None, "host-cpu"),       # the default, 16,384: 261 rows leave the mesh
    (100, "mesh"),            # a set above the option's size stays on it
], ids=["small-set", "large-set"])
def test_beside_an_accelerator_a_small_violation_set_resolves_on_the_host(
        tmp_path, trace_on, monkeypatch, host_rows, want_path):
    """On the chip the mesh backend (one process) sends a small violation
    set to the host-pinned executable, as one chip does (PERF.md section
    6, PR 29, has the reading); XLA:CPU is told here that it is one."""
    import tuplex_tpu
    from tuplex_tpu.exec import compilequeue as CQ
    from tuplex_tpu.exec import local as LB

    monkeypatch.setattr(LB, "_host_cpu_beside_accelerator", lambda: True)
    opts = dict(MESH)
    if host_rows is not None:
        opts["tuplex.tpu.hostResolveRows"] = host_rows
    ctx = tuplex_tpu.Context(opts)
    try:
        assert ctx.backend.host_resolve is True
        path, want = _listings(tmp_path, 11)
        got, stages = _job(ctx, path)
        assert Z1.compare(got, want, {}) == [
            ("rows_missing_or_extra", 0, 0), ("rows_differ", 0, 0)]
        assert all(m.get("tier") == "compiled" for m in stages)
        assert not ctx.backend.failure_log
    finally:
        ctx.close()
    evs = [e for e in trace_on.events() if e.get("dur") is not None]
    (gen,) = [e for e in evs if e["name"] == "resolve:general"]
    assert gen["args"]["path"] == want_path
    assert gen["args"]["rows"] == 261 and gen["args"]["batch"] == 640
    put_under_gen = [e for e in evs if e["name"] == "h2d:mesh-put"
                     and e["parent"] == gen["id"]]
    assert len(put_under_gen) == (1 if want_path == "mesh" else 0)
    pinned = [fp for fp, ex in CQ.executable_devices().items()
              if "/cpupin" in ex["salt"]]
    if want_path == "host-cpu":
        assert pinned


def _h2d_of_a_warm_job(ctx, path):
    """(h2d bytes of a second job, those bytes by tag)."""
    from tuplex_tpu.runtime import xferstats

    _job(ctx, path)
    x0, tags0 = xferstats.snapshot(), xferstats.tags()
    _job(ctx, path)
    return xferstats.delta(x0)["h2d_bytes"], {
        k[len("h2d_bytes:"):]: v - tags0.get(k, 0)
        for k, v in xferstats.tags().items()
        if k.startswith("h2d_bytes:") and v - tags0.get(k, 0)}


def test_h2d_bytes_count_each_batch_once(tmp_path, mesh_ctx, trace_on,
                                         monkeypatch):
    """`xferstats` counts the fast path's upload where it was staged
    (`leaf_stage`) and the general tier's under a tag of its own; the
    `h2d:mesh-put` spans carry the same bytes and add nothing. On one chip
    the same rule: per-leaf staging counts the tier's batch, a packed
    dispatch notes its own single buffer and nothing is counted twice."""
    import tuplex_tpu
    from tuplex_tpu.runtime import xferstats

    path, _ = _listings(tmp_path, 11)
    for packed in ("0", "1"):
        monkeypatch.setenv("TUPLEX_PACK_TRANSFERS", packed)
        ctx = tuplex_tpu.Context()
        try:
            moved, tags = _h2d_of_a_warm_job(ctx, path)
        finally:
            ctx.close()
        assert moved == sum(tags.values()), tags
        assert ("general_stage" in tags) == (packed == "0"), tags
        assert ("packed_dispatch" in tags) == (packed == "1"), tags
    monkeypatch.delenv("TUPLEX_PACK_TRANSFERS")

    _job(mesh_ctx, path)
    x0, tags0, t0 = xferstats.snapshot(), xferstats.tags(), trace_on.now_us()
    _job(mesh_ctx, path)
    moved = xferstats.delta(x0)["h2d_bytes"]
    tags = {k: v - tags0.get(k, 0) for k, v in xferstats.tags().items()}
    put = sum(e["args"]["bytes"] for e in trace_on.events_since(t0)
              if e["name"] == "h2d:mesh-put" and e.get("dur") is not None)
    assert moved == put
    assert tags["h2d_bytes:general_stage"] > 0
    assert moved == tags["h2d_bytes:leaf_stage"] \
        + tags["h2d_bytes:general_stage"]


def test_pad_batch_opens_a_span_only_where_it_copies(trace_on):
    from tuplex_tpu.parallel import mesh as M

    even = {"#rowvalid": np.ones(8, bool), "a": np.arange(8)}
    assert M.pad_batch_for_mesh(even, 4) is even
    assert not [e for e in trace_on.events() if e["name"] == "mesh:pad-batch"]
    odd = {"#rowvalid": np.ones(6, bool), "a": np.arange(6),
           "#seed": np.uint32(7)}
    out = M.pad_batch_for_mesh(odd, 4)
    assert out["a"].shape == (8,) and not out["#rowvalid"][6:].any()
    (e,) = [e for e in trace_on.events() if e["name"] == "mesh:pad-batch"]
    assert e["cat"] == "xfer" and e["args"] == {"rows": 6, "batch": 8}


def test_tracing_off_allocates_nothing_on_the_new_sites():
    """The mesh put, the pad and the sharded fetch with tracing off: the
    shared no-op span, no per-call growth inside runtime/tracing.py."""
    import tracemalloc

    from tuplex_tpu.exec import local as LB
    from tuplex_tpu.parallel import mesh as M
    from tuplex_tpu.runtime import tracing

    tracing.enable(False)
    tracing.clear()
    mesh = M.make_mesh(4)
    fn = M.shard_stage_fn(lambda a: {"#err": a["x"] * 0, "y": a["x"] + 1},
                          mesh, tag="noalloc")
    odd = {"#rowvalid": np.ones(6, bool), "x": np.arange(6)}

    def hot():
        outs = fn(M.pad_batch_for_mesh(odd, 4))
        return LB._get_outs(outs)

    for _ in range(8):
        got = hot()
    assert got["y"].tolist()[:6] == [1, 2, 3, 4, 5, 6]
    tracemalloc.start()
    before = tracemalloc.take_snapshot()
    for _ in range(300):
        hot()
    after = tracemalloc.take_snapshot()
    tracemalloc.stop()
    grown = sum(s.size_diff for s in after.compare_to(before, "lineno")
                if s.size_diff > 0 and any(
                    (f.filename or "").replace(os.sep, "/")
                    .endswith("runtime/tracing.py") for f in s.traceback))
    assert grown < 512, f"the new sites allocated {grown} bytes/300 calls"
    assert tracing.events() == []
