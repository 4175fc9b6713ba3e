"""Device-resident handoff through joins and aggregates + the lazy
(deferred-D2H) stage boundary.

The tentpole contract: a map -> join -> aggregate pipeline crosses BOTH
stage boundaries without a host round-trip of the intermediate data
columns — only the join-key column is ever pulled (for the host-side
signature factorization), and the join output feeds the aggregate
entirely from its device view. Every lazy leaf force is a `d2h:lazy-load`
span naming the leaf (and a count in HANDOFF_STATS), so the test asserts
the absence of transfers, not just timings."""

import numpy as np
import pytest

from tuplex_tpu.core import typesys as T
from tuplex_tpu.runtime import columns as C
from tuplex_tpu.runtime import tracing, xferstats


@pytest.fixture()
def handoff_ctx(monkeypatch):
    monkeypatch.setenv("TUPLEX_DEVICE_HANDOFF", "1")
    import tuplex_tpu

    C.HANDOFF_STATS["lazy_parts"] = 0
    C.HANDOFF_STATS["forced"] = 0
    tracing.clear()
    tracing.enable(True)
    yield tuplex_tpu.Context({"tuplex.tpu.deviceJoin": "true"})
    tracing.enable(False)
    tracing.clear()


def _lazy_loads() -> list:
    """(tag, leaf, bytes) of every `d2h:lazy-load` span; one a forced
    leaf, as HANDOFF_STATS counts them."""
    loads = [(e["args"]["tag"], e["args"]["leaf"], e["args"]["bytes"])
             for e in tracing.events() if e["name"] == "d2h:lazy-load"]
    assert len(loads) == C.HANDOFF_STATS["forced"]
    return loads


def _join_csvs(tmp_path, n=5000, keys=50):
    lp, rp = tmp_path / "l.csv", tmp_path / "r.csv"
    with open(lp, "w") as f:
        f.write("id,val,name\n")
        for i in range(n):
            f.write(f"{i % keys},{i},row{i}\n")
    with open(rp, "w") as f:
        f.write("id,tag\n")
        for i in range(keys):
            f.write(f"{i},t{i}\n")
    return str(lp), str(rp)


def test_map_join_aggregate_no_host_roundtrip(handoff_ctx, tmp_path):
    ctx = handoff_ctx
    lp, rp = _join_csvs(tmp_path)
    left = ctx.csv(lp).map(lambda x: {"id": x["id"], "v": x["val"] * 2})
    got = left.join(ctx.csv(rp), "id", "id").aggregate(
        lambda a, b: a + b, lambda a, x: a + x["v"], 0).collect()
    assert got == [sum(i * 2 for i in range(5000))]
    # both intermediates (map output, join output) went device-resident
    assert C.HANDOFF_STATS["lazy_parts"] >= 2
    # the ONLY host pull is the join-key column of the map output (leaf
    # path "0" = 'id'): no other map column, and NO join-output column,
    # ever crossed to host
    for tag, key, _ in _lazy_loads():
        assert tag == "stage" and key.split("#")[0] == "0", (tag, key)


def test_map_join_aggregate_by_key_handoff(handoff_ctx, tmp_path):
    ctx = handoff_ctx
    lp, rp = _join_csvs(tmp_path, n=3000, keys=10)
    left = ctx.csv(lp).map(lambda x: {"id": x["id"], "v": x["val"]})
    ds = left.join(ctx.csv(rp), "id", "id").aggregateByKey(
        lambda a, b: a + b, lambda a, x: a + x["v"], 0, ["tag"])
    got = dict(ds.collect())
    want: dict = {}
    for i in range(3000):
        want[f"t{i % 10}"] = want.get(f"t{i % 10}", 0) + i
    assert got == want
    assert C.HANDOFF_STATS["lazy_parts"] >= 2
    # grouped aggregate over the device-resident join output touches only
    # its KEY column ('tag' = output leaf path "2"); map-output pulls stay
    # confined to its join key ("0")
    for tag, key, _ in _lazy_loads():
        base = key.split("#")[0]
        assert (tag, base) in (("stage", "0"), ("join", "2")), (tag, key)


def test_left_join_aggregate_handoff(handoff_ctx, tmp_path):
    ctx = handoff_ctx
    lp, rp = tmp_path / "l.csv", tmp_path / "r.csv"
    with open(lp, "w") as f:
        f.write("id,val\n")
        for i in range(2000):
            f.write(f"{i % 8},{i}\n")       # keys 4..7 unmatched
    with open(rp, "w") as f:
        f.write("id,tag\n")
        for i in range(4):
            f.write(f"{i},t{i}\n")
    got = ctx.csv(str(lp)).leftJoin(ctx.csv(str(rp)), "id", "id") \
        .aggregate(lambda a, b: a + b, lambda a, x: a + x["val"],
                   0).collect()
    assert got == [sum(range(2000))]


def test_lazy_partition_collect_matches_host(handoff_ctx, tmp_path):
    # terminal collect after a handoff boundary forces the lazy leaves —
    # values must be identical to a run with handoff off
    ctx = handoff_ctx
    lp, rp = _join_csvs(tmp_path, n=800, keys=7)
    left = ctx.csv(lp).map(lambda x: {"id": x["id"], "v": x["val"] + 1})
    x0, t0 = xferstats.snapshot(), xferstats.tags()
    got = left.join(ctx.csv(rp), "id", "id").collect()
    # each forced leaf is one `d2h:lazy-load` span; its bytes are those
    # noted under the `lazy_load` tag, and the total counts every tag
    loads = _lazy_loads()
    assert loads and {tag for tag, _, _ in loads} <= {"stage", "join"}
    t1 = xferstats.tags()
    moved = {k: t1[k] - t0.get(k, 0) for k in t1
             if k.startswith("d2h_bytes:") and t1[k] != t0.get(k, 0)}
    assert moved["d2h_bytes:lazy_load"] == sum(b for _, _, b in loads)
    assert xferstats.delta(x0)["d2h_bytes"] == sum(moved.values())
    # a box span counts the loads forced under it (the join's probe
    # forced its key before any boxing began)
    evs = tracing.events()
    for box in (e for e in evs if e["name"] == "collect:box-partition"):
        under = [e for e in evs if e["name"] == "d2h:lazy-load"
                 and e["parent"] == box["id"]]
        assert box["args"]["lazy_loads"] == len(under)

    import tuplex_tpu

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TUPLEX_DEVICE_HANDOFF", "0")
        ctx2 = tuplex_tpu.Context({"tuplex.tpu.deviceJoin": "true"})
        left2 = ctx2.csv(lp).map(lambda x: {"id": x["id"],
                                            "v": x["val"] + 1})
        want = left2.join(ctx2.csv(rp), "id", "id").collect()
    assert sorted(got) == sorted(want)


def test_handoff_rerun_stable(handoff_ctx, tmp_path):
    # second execution reuses the jit cache; device views are one-shot so
    # the rerun must re-derive them without stale state
    ctx = handoff_ctx
    lp, rp = _join_csvs(tmp_path, n=1200, keys=6)
    left = ctx.csv(lp).map(lambda x: {"id": x["id"], "v": x["val"]})
    ds = left.join(ctx.csv(rp), "id", "id").aggregate(
        lambda a, b: a + b, lambda a, x: a + x["v"], 0)
    assert ds.collect() == [sum(range(1200))]
    assert ds.collect() == [sum(range(1200))]


# ---------------------------------------------------------------------------
# the gathers over a stage's outputs, each jitted under its site's name
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("site", ["view", "lazy", "load"])
def test_named_gather_lowers_under_its_site(site):
    import jax.numpy as jnp

    from tuplex_tpu.exec import local

    take = getattr(local, f"take_{site}")
    idx = jnp.asarray(np.array([7, 0, 3, 3, 5], np.int32))
    for a in (jnp.arange(24, dtype=jnp.int64).reshape(8, 3),   # str bytes
              jnp.arange(8, dtype=jnp.float64) / 3,            # a number
              jnp.asarray(np.arange(8) % 3 == 0)):             # validity
        text = take.lower(a, idx).as_text().lstrip()
        assert text.startswith(f"module @jit_tpx_take_{site}"), text[:80]
        got, want = take(a, idx), jnp.take(a, idx, axis=0)
        assert got.dtype == want.dtype and got.shape == want.shape
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# LazyLeaves unit behavior
# ---------------------------------------------------------------------------

def test_lazy_leaves_partial_force():
    loaded = []

    def loader(k):
        loaded.append(k)
        return C.NumericLeaf(np.arange(3, dtype=np.int64))

    ll = C.LazyLeaves(["0", "1", "2"], loader, tag="t")
    assert set(ll) == {"0", "1", "2"}      # key iteration: no force
    assert len(ll) == 3 and "1" in ll and bool(ll)
    assert not ll.materialized()
    assert loaded == []
    _ = ll["1"]                            # single-leaf force
    assert loaded == ["1"]
    assert ll.get("9", "dflt") == "dflt"
    assert [k for k, _ in ll.items()] == ["0", "1", "2"]  # full force
    assert sorted(loaded) == ["0", "1", "2"]
    assert ll.materialized()
    assert ll._loader is None              # device refs released


def test_lazy_partition_nbytes_uses_hint():
    ll = C.LazyLeaves(["0"], lambda k: C.NumericLeaf(
        np.arange(4, dtype=np.int64)))
    ll.nbytes_hint = 12345
    p = C.Partition(schema=T.row_of(["a"], [T.I64]), num_rows=4, leaves=ll)
    assert p.nbytes() == 12345             # no force
    assert not ll.materialized()
    _ = p.leaves["0"]
    assert p.nbytes() == 32                # real bytes once materialized


# ---------------------------------------------------------------------------
# regression: `packed` flag in the dispatch trace key (ADVICE r5)
# ---------------------------------------------------------------------------

def test_packed_flag_in_dispatch_trace_key():
    import tuplex_tpu

    ctx = tuplex_tpu.Context()
    be = ctx.backend
    schema = T.row_of(["a", "s"], [T.I64, T.STR])
    part = C.build_partition([(i, f"s{i}") for i in range(16)], schema)
    spec = C.stage_partition(
        C.build_partition([(i, f"s{i}") for i in range(16)], schema),
        be.bucket_mode).spec()
    skey = "trace-key-regression/schema"
    # the PACKED variant of this stage has executed fine before...
    be.jit_cache.note_traced(("stagefn", skey, False, True), spec)

    def boom(arrays):
        raise RuntimeError("first trace of the unpacked variant fails")

    # ...so the UNPACKED variant's first call must still count as a first
    # call: a trace-time failure demotes to the interpreter instead of
    # raising (pre-fix, the shared key misclassified it as already-traced)
    res = be._dispatch_partition(part, boom, skey, False, None,
                                 packed=False)
    assert res[1] is None
    assert skey in be._not_compilable


# ---------------------------------------------------------------------------
# direct-rank probe: probe batch is chunked (ADVICE r5 HBM bound)
# ---------------------------------------------------------------------------

def test_probe_direct_chunked_matches_searchsorted():
    from tuplex_tpu.exec.joinexec import _build_probe_fn

    rng = np.random.default_rng(9)
    u, nw = 1024, 2                        # u*nw <= 2^15 -> direct path
    build = np.unique(
        rng.integers(0, 1 << 20, (u + 64, nw)).astype(np.uint64), axis=0)
    build = build[np.lexsort(build.T[::-1])][:u]
    u = build.shape[0]
    # B=10000 > chunk=2^22/(u*nw)=2048: exercises the lax.map chunking
    words = rng.integers(0, 1 << 20, (10000, nw)).astype(np.uint64)
    words[:u] = build                      # guaranteed matches
    fn = _build_probe_fn(u, nw)
    pos, matched = fn(words, build)
    pos = np.asarray(pos)
    matched = np.asarray(matched)

    bview = np.ascontiguousarray(build.astype(">u8")).view(
        [("v", np.void, nw * 8)]).ravel()
    wview = np.ascontiguousarray(words.astype(">u8")).view(
        [("v", np.void, nw * 8)]).ravel()
    ref = np.searchsorted(bview, wview)
    ref_c = np.clip(ref, 0, u - 1)
    ref_m = (ref < u) & (bview[ref_c] == wview)
    np.testing.assert_array_equal(matched, ref_m)
    np.testing.assert_array_equal(pos[ref_m], ref_c[ref_m])


# ---------------------------------------------------------------------------
# serverless warm-worker log fds are closed (ADVICE r5)
# ---------------------------------------------------------------------------

def test_warm_worker_log_closed_on_close(tmp_path, monkeypatch):
    import tuplex_tpu
    from tuplex_tpu.exec import serverless as S

    import io

    class _FakeProc:
        def __init__(self, *a, **k):
            self.stdin = io.StringIO()
            self._rc = None

        def poll(self):
            return self._rc

        def wait(self, timeout=None):
            self._rc = 0
            return 0

        def kill(self):
            self._rc = -9

    monkeypatch.setattr(S.subprocess, "Popen",
                        lambda *a, **k: _FakeProc(*a, **k))
    ctx = tuplex_tpu.Context({
        "tuplex.backend": "serverless",
        "tuplex.scratchDir": str(tmp_path)})
    be = ctx.backend
    w = be._spawn_warm()
    be._pool.append(w)
    assert w.logf is not None and not w.logf.closed
    logf = w.logf
    be.close()
    assert logf.closed
    assert be._pool == []
