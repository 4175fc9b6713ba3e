"""The merge's splice of resolved rows (`LocalBackend._splice`) and the
row gather under it (`columns.gather_partition`).

The splice builds its emit order from masks and takes each leaf in one
pass over a full cover of source rows; the oracle here is the row-by-row
form it replaced (an emit loop over every row, a zero-filled partition
and a scatter of the compiled rows into it). The two must agree byte for
byte, the padding past a string's length included: `unique_rows` and the
joins compare whole byte rows.
"""
from types import SimpleNamespace

import numpy as np
import pytest

from tuplex_tpu.core import typesys as T
from tuplex_tpu.core.row import Row
from tuplex_tpu.exec import local
from tuplex_tpu.plan.physical import runtime_output_columns
from tuplex_tpu.runtime import columns as C

N = 40


def _scatter(part, out_positions, src_indices, m):
    """Zero-filled m-row partition with rows src_indices scattered to
    out_positions."""
    leaves = {}
    for path, leaf in part.leaves.items():
        if isinstance(leaf, C.NumericLeaf):
            data = np.zeros(m, dtype=leaf.data.dtype)
            data[out_positions] = leaf.data[src_indices]
            valid = None
            if leaf.valid is not None:
                valid = np.zeros(m, np.bool_)
                valid[out_positions] = leaf.valid[src_indices]
            leaves[path] = C.NumericLeaf(data, valid)
        elif isinstance(leaf, C.StrLeaf):
            w = leaf.bytes.shape[1]
            b = np.zeros((m, max(w, 1)), dtype=np.uint8)
            b[out_positions, :w] = leaf.bytes[src_indices]
            ln = np.zeros(m, dtype=np.int32)
            ln[out_positions] = leaf.lengths[src_indices]
            valid = None
            if leaf.valid is not None:
                valid = np.zeros(m, np.bool_)
                valid[out_positions] = leaf.valid[src_indices]
            leaves[path] = C.StrLeaf(b, ln, valid)
        elif isinstance(leaf, C.NullLeaf):
            leaves[path] = C.NullLeaf(m)
        else:
            vals = [None] * m
            for o, s in zip(list(out_positions), list(src_indices)):
                vals[o] = leaf.values[s]
            leaves[path] = C.ObjectLeaf(vals)
    return C.Partition(schema=part.schema, num_rows=m, leaves=leaves,
                       start_index=part.start_index)


def _oracle_splice(stage, part, compiled_ok, out_arrays, resolved, src_map):
    """The splice row by row: emit order, zero fill, scatter, fold."""
    n = part.num_rows
    emit_rows = []
    for i in range(n):
        if i in resolved:
            emit_rows.append((i, None, resolved[i]))
        elif compiled_ok[i]:
            emit_rows.append((i, i, None))
    m = len(emit_rows)
    if not out_arrays:
        rows = [row for (_, _, row) in emit_rows]
        values = [row.unwrap() if len(row.values) == 1
                  else tuple(row.values) for row in rows]
        schema = local._schema_from_rows(rows) or \
            local._normalized_output_schema(stage)
        return C.build_partition(values, schema,
                                 start_index=part.start_index)
    n_full = n if src_map is None else \
        int(next(iter(out_arrays.values())).shape[0])
    full = C.partition_from_result_arrays(
        out_arrays, n_full,
        columns=runtime_output_columns(part.schema, stage.ops),
        start_index=part.start_index)
    comp_out = [k for k, (_, s, _) in enumerate(emit_rows) if s is not None]
    comp_src = [s for (_, s, _) in emit_rows if s is not None]
    if src_map is not None:
        comp_src = [int(src_map[s]) for s in comp_src]
    outp = _scatter(full, np.asarray(comp_out, dtype=np.int64),
                    np.asarray(comp_src, dtype=np.int64), m)
    schema = outp.schema
    res = [(k, row.unwrap() if len(schema.columns) == 1
            else tuple(row.values))
           for k, (_, _, row) in enumerate(emit_rows) if row is not None]
    if not res or local._bulk_fold_rows(
            outp.leaves, schema, np.asarray([k for k, _ in res], np.int64),
            [v for _, v in res]):
        return outp
    normal_mask = np.ones(m, dtype=np.bool_)
    fallback = {}
    for k, value in res:
        if not local._try_fold_row(outp.leaves, schema, k, value):
            normal_mask[k] = False
            fallback[k] = value
    if fallback:
        outp.normal_mask = normal_mask
        outp.fallback = fallback
    return outp


def _same_array(a, b):
    if a is None or b is None:
        assert a is None and b is None
        return
    assert a.dtype == b.dtype and a.shape == b.shape
    assert a.tobytes() == b.tobytes()


def _assert_same_partition(got, want):
    assert got.num_rows == want.num_rows
    assert got.schema == want.schema
    assert got.start_index == want.start_index
    assert list(got.leaves) == list(want.leaves)
    for path, w in want.leaves.items():
        g = got.leaves[path]
        assert type(g) is type(w), path
        if isinstance(w, C.NumericLeaf):
            _same_array(g.data, w.data)
            _same_array(g.valid, w.valid)
        elif isinstance(w, C.StrLeaf):
            _same_array(g.bytes, w.bytes)
            _same_array(g.lengths, w.lengths)
            _same_array(g.valid, w.valid)
        elif isinstance(w, C.NullLeaf):
            assert g.n == w.n
        else:
            assert g.values == w.values
    _same_array(got.normal_mask, want.normal_mask)
    assert got.fallback == want.fallback


def _device_outputs(rng, rows, opt_tuple=False, zero_width=False):
    """Stage outputs with stale bytes past every string's length: an
    int, a str, an Option[str], an Option[float], optionally an
    Option[(int, str)] (a `#opt` leaf) and a zero-width str."""
    def strs(w):
        return (rng.integers(0, 256, (rows, w), dtype=np.uint8),
                rng.integers(0, w + 1, rows).astype(np.int32))

    out = {"0": rng.integers(-10**9, 10**9, rows)}
    out["1#bytes"], out["1#len"] = strs(8)
    out["2#bytes"], out["2#len"] = strs(6)
    out["2#valid"] = rng.random(rows) < 0.7
    out["3"] = rng.standard_normal(rows)
    out["3#valid"] = rng.random(rows) < 0.7
    if opt_tuple:
        out["4#opt"] = rng.random(rows) < 0.6
        out["4.0"] = rng.integers(0, 1000, rows)
        out["4.0#valid"] = out["4#opt"].copy()
        out["4.1#bytes"], out["4.1#len"] = strs(4)
        out["4.1#valid"] = out["4#opt"].copy()
    if zero_width:
        ci = "5" if opt_tuple else "4"
        out[ci + "#bytes"] = np.zeros((rows, 0), dtype=np.uint8)
        out[ci + "#len"] = np.zeros(rows, dtype=np.int32)
    return out


def _resolved_row(rng, opt_tuple, zero_width, wide=False):
    vals = [int(rng.integers(-99, 99)), "ab" * int(rng.integers(0, 5)),
            None if rng.random() < 0.3 else "xyz"[: int(rng.integers(0, 4))],
            None if rng.random() < 0.3 else float(rng.standard_normal())]
    if wide:
        vals[1] = "much wider than eight bytes"
    if opt_tuple:
        vals.append(None if rng.random() < 0.4 else
                    (int(rng.integers(0, 9)), "q" * int(rng.integers(0, 5))))
    if zero_width:
        vals.append("")
    return Row(tuple(vals), tuple(f"c{i}" for i in range(len(vals))))


# name -> the resolved rows, the compiled rows dropped (`drop`; `ok_too`:
# the resolved ones stay compiled_ok too), the rows whose value does not
# fit (`wide`) and the layout
CASES = {
    "none_resolved": dict(res=[]),
    "one_resolved": dict(res=[17]),
    "all_resolved": dict(res=list(range(N))),
    "first_and_last": dict(res=[0, N - 1]),
    "resolved_and_compiled_ok": dict(res=[3, 4, 20], ok_too=True),
    "filtered_rows": dict(res=[5, 30], drop=[1, 2, 6, 29, 31, N - 1]),
    "compacted": dict(res=[0, 9, 10, 33], drop=[1, 11, 12], compact=True),
    "compacted_all_resolved": dict(res=list(range(N)), compact=True),
    "interpreter_only": dict(res=[2, 7, 8, 39], interp=True),
    "interpreter_only_none": dict(res=[], interp=True),
    "not_folded": dict(res=[4, 15, 16, 38], wide=[15, 38]),
    "option_tuple": dict(res=[1, 12, 13, 27], opt_tuple=True),
    "option_tuple_not_folded": dict(res=[1, 12, 27], opt_tuple=True,
                                    wide=[12]),
    "zero_width_str": dict(res=[6, 21], zero_width=True),
    "zero_width_all_resolved": dict(res=list(range(N)), zero_width=True),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_splice_matches_the_row_by_row_merge(case):
    spec = CASES[case]
    rng = np.random.default_rng(sorted(CASES).index(case) + 1)
    opt_tuple = spec.get("opt_tuple", False)
    zero_width = spec.get("zero_width", False)
    res = spec["res"]
    compiled_ok = np.ones(N, dtype=np.bool_)
    compiled_ok[spec.get("drop", [])] = False
    if not spec.get("ok_too"):
        compiled_ok[res] = False
    resolved = {i: _resolved_row(rng, opt_tuple, zero_width,
                                 wide=i in spec.get("wide", []))
                for i in res}
    src_map = None
    rows = N
    if spec.get("compact"):
        # the device kept the compiled rows alone, in order, in a bucket
        # of 48 slots; the rest of the bucket is stale
        kept = np.nonzero(compiled_ok)[0]
        src_map = np.full(N, -1, dtype=np.int64)
        src_map[kept] = np.arange(kept.size)
        rows = 48
    out_arrays = {} if spec.get("interp") else \
        _device_outputs(rng, rows, opt_tuple, zero_width)
    if spec.get("interp"):
        compiled_ok[:] = False
    part = C.Partition(schema=T.row_of(("line",), (T.STR,)), num_rows=N,
                       leaves={}, start_index=1000)
    stage = SimpleNamespace(ops=[], output_columns=("a", "b"),
                            output_schema=T.row_of(("a", "b"),
                                                   (T.I64, T.STR)))
    want = _oracle_splice(stage, part, compiled_ok, out_arrays, resolved,
                          src_map)
    got = local.LocalBackend._splice(None, stage, part, compiled_ok,
                                     out_arrays, resolved, src_map)
    _assert_same_partition(got, want)
    if spec.get("wide"):
        assert set(got.fallback) and not got.normal_mask.all()


def _leaf_kinds(rng, n):
    strs = rng.integers(0, 256, (n, 5), dtype=np.uint8)
    lens = rng.integers(0, 6, n).astype(np.int32)
    return {
        "int": C.NumericLeaf(rng.integers(-50, 50, n)),
        "float_opt": C.NumericLeaf(rng.standard_normal(n),
                                   rng.random(n) < 0.5),
        "bool_opt_flag": C.NumericLeaf(rng.random(n) < 0.5),
        "str": C.StrLeaf(strs, lens),
        "str_opt": C.StrLeaf(strs[:, :3].copy(), np.minimum(lens, 3),
                             rng.random(n) < 0.5),
        "str_zero_width": C.StrLeaf(np.zeros((n, 0), np.uint8),
                                    np.zeros(n, np.int32)),
        "null": C.NullLeaf(n),
        "object": C.ObjectLeaf([[i, "x"] for i in range(n)]),
    }


@pytest.mark.parametrize("kind", sorted(_leaf_kinds(np.random.default_rng(0),
                                                    1)))
def test_gather_partition_full_cover_takes_what_the_scatter_gives(kind):
    rng = np.random.default_rng(7)
    n, m = 30, 22
    part = C.Partition(schema=T.row_of(("a",), (T.I64,)), num_rows=n,
                       leaves={"0": _leaf_kinds(rng, n)[kind]},
                       start_index=5)
    src = rng.integers(0, n, m)
    # 0..m-1 is a full cover: one take a leaf
    taken = C.gather_partition(part, np.arange(m, dtype=np.int64), src, m)
    # the same cover in another order is not 0..m-1: zero fill + scatter
    perm = rng.permutation(m)
    scattered = C.gather_partition(part, perm, src[perm], m)
    _assert_same_partition(taken, scattered)
    _assert_same_partition(taken, _scatter(part, np.arange(m), src, m))
    # a partial cover leaves its open slots zero
    pos = np.sort(rng.choice(m, 9, replace=False))
    some = C.gather_partition(part, pos, src[:9], m)
    _assert_same_partition(some, _scatter(part, pos, src[:9], m))
    open_ = np.setdiff1d(np.arange(m), pos)
    leaf = some.leaves["0"]
    if isinstance(leaf, C.NumericLeaf):
        assert not leaf.data[open_].any()
        assert leaf.valid is None or not leaf.valid[open_].any()
    elif isinstance(leaf, C.StrLeaf):
        assert leaf.bytes.shape[1] >= 1
        assert not leaf.bytes[open_].any() and not leaf.lengths[open_].any()
        assert leaf.valid is None or not leaf.valid[open_].any()
    elif isinstance(leaf, C.ObjectLeaf):
        assert all(leaf.values[o] is None for o in open_)
