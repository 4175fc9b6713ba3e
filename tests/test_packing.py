"""Single-buffer transfer packing (runtime/packing.py): round-trip
exactness for every leaf dtype the stage runtime ships, including the
64-bit split-into-u32-halves path (the XLA-TPU x64 legalizer cannot
rewrite 64-bit bitcast-convert inside large graphs) and the f64
per-leaf bypass (f64->int bitcasts fail outright on the TPU stack)."""

import numpy as np
import pytest


@pytest.fixture()
def packed_identity():
    from tuplex_tpu.runtime.packing import PackedOuts, PackedStageFn

    fn = PackedStageFn(lambda arrays: dict(arrays), donate=False)

    def roundtrip(arrays):
        out = fn(arrays)
        assert isinstance(out, PackedOuts)
        return out.to_host()

    return roundtrip


def test_packing_roundtrip_all_dtypes(packed_identity):
    rng = np.random.default_rng(7)
    arrays = {
        "u8": rng.integers(0, 256, (257, 13), np.uint8),
        "bool": rng.integers(0, 2, (300,)).astype(np.bool_),
        "i32": rng.integers(-2**31, 2**31 - 1, (99,), np.int64)
        .astype(np.int32),
        "u32": rng.integers(0, 2**32 - 1, (64, 3), np.uint64)
        .astype(np.uint32),
        "f32": rng.standard_normal((41,)).astype(np.float32),
        "i64": np.array([0, 1, -1, 2**62, -2**62, 1234567890123], np.int64),
        "u64": np.array([0, 1, 2**63, 2**64 - 1, 0xDEADBEEFCAFEF00D],
                        np.uint64),
        "f64": rng.standard_normal((55,)),          # per-leaf bypass
        "scalar": np.bool_(True).reshape(()),
        "empty": np.zeros((0, 4), np.uint8),
    }
    got = packed_identity(arrays)
    assert set(got) == set(arrays)
    for k, want in arrays.items():
        g = np.asarray(got[k])
        assert g.dtype == want.dtype, k
        assert g.shape == want.shape, k
        np.testing.assert_array_equal(g, want, err_msg=k)


def _str_matrix(rng, n, w, lens=None):
    """Canonical StrLeaf byte matrix: random content, zero past len (the
    columnar contract — signatures/decode never read past the length, and
    the varlen wire ships only the content bytes)."""
    lens = rng.integers(0, w + 1, (n,)).astype(np.int32) \
        if lens is None else lens
    mat = rng.integers(1, 256, (n, w), np.uint8)
    mat = np.where(np.arange(w)[None, :] < lens[:, None], mat, 0)
    return mat.astype(np.uint8), lens


def test_packing_narrowed_len_wire(packed_identity):
    # '#len' i32 columns ride the wire as u16 when their '#bytes' sibling
    # width fits; '#err' must NOT narrow (op ids exceed u16)
    from tuplex_tpu.runtime import packing as P

    rng = np.random.default_rng(3)
    mat, lens = _str_matrix(rng, 100, 40)
    mat16, lens16 = _str_matrix(rng, 100, 1000)
    arrays = {
        "0#bytes": mat,
        "0#len": lens,                                 # W <= 255: u8
        "m#bytes": mat16,
        "m#len": lens16,                               # 255 < W < 2^16: u16
        "wide#bytes": np.zeros((10, 1 << 16), np.uint8),
        "wide#len": np.full((10,), 70000, np.int32),   # > u16: stays i32
        "#err": (np.arange(100, dtype=np.int32) + (300 << 8)),  # op id 300
    }
    spec, _ = P._host_spec(arrays)
    wire = {s[0]: s[5] for s in spec}
    assert np.dtype(wire["0#len"]) == np.uint8
    assert np.dtype(wire["m#len"]) == np.uint16
    assert np.dtype(wire["wide#len"]) == np.int32
    assert np.dtype(wire["#err"]) == np.int32
    got = packed_identity(arrays)
    for k, want in arrays.items():
        g = np.asarray(got[k])
        assert g.dtype == want.dtype, k
        np.testing.assert_array_equal(g, want, err_msg=k)


def test_packing_f64_rides_per_leaf(packed_identity):
    from tuplex_tpu.runtime import packing as P

    arrays = {"a": np.arange(8, dtype=np.float64),
              "b": np.arange(8, dtype=np.int64)}
    spec, _ = P._host_spec(arrays)
    packed_keys = {s[0] for s in spec}
    assert packed_keys == {"b"}          # f64 bypasses the buffer


def test_packing_empty_dict(packed_identity):
    assert packed_identity({}) == {}


# ---------------------------------------------------------------------------
# varlen wire (offsets+payload instead of padded [B, W] matrices)
# ---------------------------------------------------------------------------

def _varlen_roundtrip(arrays):
    from tuplex_tpu.runtime.packing import PackedOuts, PackedStageFn

    fn = PackedStageFn(lambda a: dict(a), donate=False)
    out = fn(arrays)
    assert isinstance(out, PackedOuts)
    return out, out.to_host()


def test_varlen_roundtrip_device_to_host():
    # device varlen pack -> host unpack: empty strings, max-width rows,
    # and ordinary mixed lengths all round-trip exactly
    rng = np.random.default_rng(11)
    w = 48
    mat, lens = _str_matrix(rng, 300, w)
    lens[0] = 0                    # empty string
    mat[0] = 0
    lens[1] = w                    # max-width row
    mat[1] = rng.integers(1, 256, w, np.uint8)
    mat2, lens2 = _str_matrix(rng, 300, 16)
    arrays = {"0#bytes": mat, "0#len": lens,
              "1#bytes": mat2, "1#len": lens2,
              "2": rng.integers(-5, 5, 300),
              "#err": np.zeros(300, np.int32)}
    out, got = _varlen_roundtrip(arrays)
    vkinds = {k: kind for kind, k, _, _ in out.vspec}
    assert vkinds["0#bytes"] == "str" and vkinds["1#bytes"] == "str"
    assert vkinds["2"] == "hi32"           # 1-D i64: lo/hi split wire
    assert vkinds["#err"] == "sparse32"    # zero-dominated lattice
    for k, want in arrays.items():
        g = np.asarray(got[k])
        assert g.dtype == want.dtype, k
        np.testing.assert_array_equal(g, want, err_msg=k)


def test_varlen_all_empty_and_zero_rows():
    arrays = {"0#bytes": np.zeros((64, 8), np.uint8),
              "0#len": np.zeros(64, np.int32),
              "1#bytes": np.zeros((0, 4), np.uint8),
              "1#len": np.zeros(0, np.int32)}
    out, got = _varlen_roundtrip(arrays)
    for k, want in arrays.items():
        np.testing.assert_array_equal(np.asarray(got[k]), want, err_msg=k)


def test_varlen_u16_boundary_len():
    # len == 2^16-1 is the last value that narrows to u16; the width must
    # be >= the len for the wire to carry it (W bounds len by contract)
    n = 4
    w = (1 << 16) - 1
    lens = np.full(n, w, np.int32)
    mat = np.ones((n, w), np.uint8)
    arrays = {"0#bytes": mat, "0#len": lens}
    from tuplex_tpu.runtime import packing as P

    spec, _ = P._host_spec(arrays)
    wire = {s[0]: s[5] for s in spec}
    assert np.dtype(wire["0#len"]) == np.uint16   # 65535 still fits
    out, got = _varlen_roundtrip(arrays)
    np.testing.assert_array_equal(np.asarray(got["0#len"]), lens)
    np.testing.assert_array_equal(np.asarray(got["0#bytes"]), mat)


def test_u16_narrowing_invariant_validated_on_host():
    # a '#len' leaf violating the len<=width invariant (out of the
    # narrowed range, or negative) must fall back to i32 on the host pack
    # path instead of silently wrapping (ADVICE r5)
    from tuplex_tpu.runtime import packing as P

    base = {"0#bytes": np.zeros((8, 100), np.uint8)}
    for bad in (np.full(8, 1 << 16, np.int32),
                np.full(8, 300, np.int32),     # > u8 range for W=100
                np.full(8, -3, np.int32)):
        arrays = dict(base)
        arrays["0#len"] = bad
        spec, total = P._host_spec(arrays)
        wire = {s[0]: s[5] for s in spec}
        assert np.dtype(wire["0#len"]) == np.int32, bad[0]
        buf = P._pack_host(arrays, spec, total)
        got = P._unpack_host(buf, spec)
        np.testing.assert_array_equal(got["0#len"], bad)
    good = dict(base)
    good["0#len"] = np.full(8, 99, np.int32)
    spec, _ = P._host_spec(good)
    assert np.dtype({s[0]: s[5] for s in spec}["0#len"]) == np.uint8
    wide = {"0#bytes": np.zeros((8, 1000), np.uint8),
            "0#len": np.full(8, 700, np.int32)}
    spec, _ = P._host_spec(wide)
    assert np.dtype({s[0]: s[5] for s in spec}["0#len"]) == np.uint16


def test_varlen_wire_shrinks_padded_strings():
    # zillow-shaped leaves (wide padded matrices, short content) must ship
    # >= 3x fewer D2H bytes on the varlen wire than fixed-width packing
    from tuplex_tpu.runtime import xferstats
    from tuplex_tpu.runtime.packing import PackedStageFn

    rng = np.random.default_rng(5)
    n, w = 2048, 256
    lens = rng.integers(5, 30, n).astype(np.int32)   # ~20B of content
    mat = np.where(np.arange(w)[None, :] < lens[:, None],
                   rng.integers(1, 256, (n, w), np.uint8), 0).astype(np.uint8)
    arrays = {"0#bytes": mat, "0#len": lens,
              "1": rng.integers(0, 9, n), "#err": np.zeros(n, np.int32)}

    def measure(env_val, monkey):
        monkey.setenv("TUPLEX_VARLEN_WIRE", env_val)
        fn = PackedStageFn(lambda a: dict(a), donate=False)
        snap = xferstats.snapshot()
        got = fn(arrays).to_host()
        for k in arrays:
            np.testing.assert_array_equal(np.asarray(got[k]), arrays[k], k)
        return xferstats.delta(snap)["d2h_bytes"]

    import pytest

    mp = pytest.MonkeyPatch()
    try:
        fixed = measure("0", mp)
        varlen = measure("1", mp)
    finally:
        mp.undo()
    assert varlen * 3 <= fixed, (varlen, fixed)


def test_strleaf_wire_view_roundtrip():
    from tuplex_tpu.runtime import columns as C

    leaf = C.encode_str_leaf(["", "hello", "x" * 31, None, "df"],
                             optional=True)
    payload, lens = leaf.to_wire()
    assert payload.nbytes == int(np.clip(leaf.lengths, 0,
                                         leaf.width).sum())
    back = C.StrLeaf.from_wire(payload, lens, leaf.width, leaf.valid)
    np.testing.assert_array_equal(back.bytes, leaf.bytes)
    np.testing.assert_array_equal(back.lengths, leaf.lengths)
    for i in range(5):
        assert C.decode_str(back, i) == C.decode_str(leaf, i)


# ---------------------------------------------------------------------------
# the unpack's two routes: one native call a partition (`unpack_varlen`),
# `columns.varlen_to_matrix` an entry where the module is not loaded
# ---------------------------------------------------------------------------

@pytest.fixture(params=[True, False], ids=["native", "no-native"])
def unpack_route(request, monkeypatch):
    """Steer `native.get()` as `TUPLEX_TPU_NO_NATIVE` does at a process's
    start; hands back whether the native call is the route."""
    from tuplex_tpu import native as N

    if not request.param:
        monkeypatch.setenv("TUPLEX_TPU_NO_NATIVE", "1")
        monkeypatch.setattr(N, "_mod", None)
        monkeypatch.setattr(N, "_tried", False)
        assert N.get() is None
    elif N.get() is None or not hasattr(N.get(), "unpack_varlen"):
        pytest.skip("no compiler available")
    return request.param


def _lattice(n, dead=()):
    """The stage lattice that gives the packer a `#live` mask: every row
    valid, kept and clean but `dead`, which fail one of the three by
    turns (the `#err` codes of those that fail it ride `sparse32`)."""
    keep = np.ones(n, np.bool_)
    err = np.zeros(n, np.int32)
    rowvalid = np.ones(n, np.bool_)
    for j, i in enumerate(dead):
        if j % 3 == 0:
            keep[i] = False
        elif j % 3 == 1:
            err[i] = 7 | (300 + j) << 8
        else:
            rowvalid[i] = False
    return {"#keep": keep, "#err": err, "#rowvalid": rowvalid}


def _unpack_case(case):
    """(arrays, the arrays `to_host` must give back): the wire drops what
    lies past a row's clamped length and every varlen byte of a dead row;
    a padding row's `#err` code is noise and reads 0."""
    rng = np.random.default_rng(len(case))
    n, w = 300, 24
    mat, lens = _str_matrix(rng, n, w)
    if case == "empty-strings":
        lens[::3] = 0
        mat[::3] = 0
        return ({"0#bytes": mat, "0#len": lens},) * 2
    if case == "max-width-rows":
        lens[:] = w
        mat = rng.integers(1, 256, (n, w), np.uint8)
        return ({"0#bytes": mat, "0#len": lens},) * 2
    if case == "length-past-w":
        # past the width but inside the narrowed u8 wire: clamped to the
        # width on the device and on the host alike, `#len` left as it is
        mat = rng.integers(1, 256, (n, w), np.uint8)
        lens[:] = w
        lens[5], lens[6] = 30, 255
        return ({"0#bytes": mat, "0#len": lens},) * 2
    if case == "zero-rows":
        return ({"0#bytes": np.zeros((0, w), np.uint8),
                 "0#len": np.zeros(0, np.int32),
                 "1": np.zeros(0, np.int64)},) * 2
    if case == "all-empty-column":
        mat2, lens2 = _str_matrix(rng, n, 8)
        return ({"0#bytes": np.zeros((n, w), np.uint8),
                 "0#len": np.zeros(n, np.int32),
                 "1#bytes": mat2, "1#len": lens2},) * 2
    dead = list(range(2, n, 7)) if case == "live-mask" else [1, 2, 3, 298]
    arrays = {"0#bytes": mat, "0#len": lens, **_lattice(n, dead)}
    if case == "all-four-kinds":
        mat2, lens2 = _str_matrix(rng, n, 1)
        i64 = rng.integers(-2**62, 2**62, n)       # `hi32` and `lo32v`
        i64[::2] = rng.integers(-9, 9, n // 2 + n % 2)
        u64 = rng.integers(0, 2**63, n).astype(np.uint64) * np.uint64(2)
        arrays.update({"1#bytes": mat2, "1#len": lens2, "2": i64, "3": u64})
    want = {k: v.copy() for k, v in arrays.items()}
    for k in ("0#bytes", "1#bytes", "2", "3"):
        if k in want:
            want[k][dead] = 0
    want["#err"][~arrays["#rowvalid"]] = 0
    return arrays, want


_UNPACK_CASES = ["empty-strings", "max-width-rows", "length-past-w",
                 "live-mask", "zero-rows", "all-empty-column",
                 "all-four-kinds"]


@pytest.mark.parametrize("case", _UNPACK_CASES)
def test_varlen_unpack_gives_the_same_bytes_on_both_routes(
        unpack_route, case):
    from tuplex_tpu import native as N

    arrays, want = _unpack_case(case)
    out, got = _varlen_roundtrip(arrays)
    kinds = {kind for kind, _k, _shape, _dt in out.vspec}
    assert "str" in kinds
    if case == "all-four-kinds":
        assert kinds == {"str", "hi32", "lo32v", "sparse32"}
    assert set(got) == set(want)
    for k, w in want.items():
        g = np.asarray(got[k])
        assert g.dtype == w.dtype and g.shape == w.shape, k
        np.testing.assert_array_equal(g, w, err_msg=k)
        if k.endswith("#bytes"):
            assert g.flags.writeable and g.flags.c_contiguous, k
    if unpack_route:
        # and byte for byte what the numpy twin makes of the same buffers
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(N, "_mod", None)
            mp.setattr(N, "_tried", True)
            twin = out.to_host()
        for k in got:
            assert np.asarray(twin[k]).tobytes() == \
                np.asarray(got[k]).tobytes(), k


def test_varlen_unpack_span_says_which_route_built_the_matrices(
        unpack_route):
    from tuplex_tpu.runtime import tracing

    arrays, _ = _unpack_case("all-four-kinds")
    out, _ = _varlen_roundtrip(arrays)
    tracing.clear()
    tracing.enable(True)
    try:
        out.to_host()
    finally:
        tracing.enable(False)
    found = [e for e in tracing.events() if e["name"] == "d2h:varlen-unpack"]
    tracing.clear()
    (sp,) = found
    assert sp["args"]["native"] == int(unpack_route)
    assert sp["args"]["entries"] == len(out.vspec) == 7
    assert sp["args"]["bytes"] > 0


@pytest.mark.parametrize("fault", ["past-the-payload", "length-past-width",
                                   "negative-length", "short-output",
                                   "read-only-output"])
def test_native_unpack_varlen_refuses_what_it_cannot_read_in_bounds(fault):
    """The native entry never reads past the payload nor writes past a
    matrix: lengths that sum past the payload's end, or lie outside
    [0, width], raise `ValueError` (the twin clips its reads)."""
    from tuplex_tpu import native as N

    nat = N.get()
    if nat is None or not hasattr(nat, "unpack_varlen"):
        pytest.skip("no compiler available")
    payload = np.arange(1, 41, dtype=np.uint8)
    lens = np.array([4, 0, 8, 3], np.int64)
    out = np.full((4, 8), 0xEE, np.uint8)
    second = (np.array([8, 8, 8], np.int64), 8, np.empty((3, 8), np.uint8))
    assert nat.unpack_varlen(payload, [(lens, 8, out), second]) == 39
    assert out[0].tolist() == [1, 2, 3, 4, 0, 0, 0, 0]
    assert not out[1].any() and out[2].tolist() == list(range(5, 13))
    assert second[2][2].tolist() == list(range(32, 40))
    if fault == "past-the-payload":
        args = (payload[:38], [(lens, 8, out), second])
    elif fault == "length-past-width":
        args = (payload, [(np.array([4, 9], np.int64), 8, out[:2])])
    elif fault == "negative-length":
        args = (payload, [(np.array([4, -1], np.int64), 8, out[:2])])
    elif fault == "short-output":
        args = (payload, [(lens, 8, out[:3])])
    else:
        frozen = out.copy()
        frozen.flags.writeable = False
        args = (payload, [(lens, 8, frozen)])
    with pytest.raises(TypeError if fault == "read-only-output"
                       else ValueError):
        nat.unpack_varlen(*args)


# ---------------------------------------------------------------------------
# the payload leaves the executable as whole chunk buffers (PR 36): the
# fetch of payload[:total] takes no executable of its own
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("nbytes, chunks, chunk", [
    (0, 1, 64), (1, 1, 64), (65535, 1, 65536), (65536, 1, 65536),
    (3 * 65536 + 1, 3, 65600),
    (18_464_256, 16, 1_154_048),         # Z1's stage at 114,688 slots
    (1 << 30, 16, 1 << 26),
])
def test_payload_chunking(nbytes, chunks, chunk):
    from tuplex_tpu.runtime.packing import _payload_chunking

    assert _payload_chunking(nbytes) == (chunks, chunk)
    assert chunks * chunk >= nbytes and chunk % 64 == 0


@pytest.mark.parametrize("fill", [0.0, 0.2, 0.55, 1.0],
                         ids=lambda f: f"fill{f}")
def test_varlen_fetch_takes_whole_chunks_and_no_device_op(fill, monkeypatch):
    """A payload several chunks long comes back exact whichever chunk the
    content ends in, the fetch hands `jax.device_get` whole output buffers
    of the executable (never a slice: that would be an executable of its
    own, queued behind every dispatch in flight) and fetches no chunk past
    the content's end."""
    import jax

    from tuplex_tpu.runtime import packing

    rng = np.random.default_rng(36)
    n, w = 4096, 96                        # capacity 393,216 B + '#err'
    lens = np.minimum(rng.integers(0, w + 1, n),
                      int(round(fill * w))).astype(np.int32)
    mat, lens = _str_matrix(rng, n, w, lens)
    arrays = {"0#bytes": mat, "0#len": lens,
              "#err": np.zeros(n, np.int32)}
    out = packing.PackedStageFn(lambda a: dict(a), donate=False)(arrays)
    assert len(out.vbuf) == 6 and len({int(c.shape[0]) for c in out.vbuf}) == 1
    chunk = int(out.vbuf[0].shape[0])
    whole = {id(c) for c in out.vbuf} | {id(out.buf)}
    fetched = []
    real = jax.device_get

    def spy(x):
        fetched.extend(jax.tree_util.tree_leaves(x))
        return real(x)

    monkeypatch.setattr(packing.jax, "device_get", spy)
    got = out.to_host()
    assert all(id(a) in whole for a in fetched)
    assert len(fetched) - 1 == -(-int(lens.sum()) // chunk)
    for k, want in arrays.items():
        np.testing.assert_array_equal(np.asarray(got[k]), want, err_msg=k)
