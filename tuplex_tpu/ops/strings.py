"""Vectorized string kernels over fixed-width byte matrices.

The TPU-native replacement for the reference's compiled string runtime
(reference: tuplex/runtime/src/StringFunctions.cc:76-439 — SIMD strLower etc.,
and codegen'd str methods in codegen/include/FunctionRegistry.h:71-205).

Representation: a batch of N strings is (bytes: uint8 [N, W] zero-padded,
lens: int32 [N]). All kernels are shape-static jnp programs — constant
needles/widths are baked into the trace (they come from UDF constants, which
the data-driven compiler specializes on, exactly like the reference bakes
constants into LLVM IR).

Conventions:
  * kernels never raise — they return (result..., err) or sentinel values;
    the emitter turns sentinels into error-code lattice updates
  * positions use int32; -1 means "not found" (Python find semantics)
"""

from __future__ import annotations

import numpy as np

from ..runtime.jaxcfg import f64_is_f32_pair, jnp, lax


def const_bytes(s: str) -> np.ndarray:
    return np.frombuffer(s.encode("utf-8"), dtype=np.uint8)


def broadcast_const(s: str, n: int, width: int | None = None):
    """Materialize a python str constant as an [n, W] batch."""
    b = const_bytes(s)
    w = max(len(b), 1) if width is None else width
    mat = np.zeros((1, w), dtype=np.uint8)
    mat[0, : len(b)] = b
    return (
        jnp.broadcast_to(jnp.asarray(mat), (n, w)),
        jnp.full((n,), len(b), dtype=jnp.int32),
    )


def _pos_mask(width: int, lens):
    """[N, width] bool — True where position < len."""
    return jnp.arange(width, dtype=jnp.int32)[None, :] < lens[:, None]


import contextvars

# per-trace override: _CpuJit (exec/local.py) traces host-CPU executables
# while the process default backend is still the accelerator, so the
# backend check below would wrongly pick the MXU formulations there
_MXU_OVERRIDE: contextvars.ContextVar = contextvars.ContextVar(
    "tuplex_mxu_gather", default=None)


def mxu_gather_override(value):
    """Context manager forcing the MXU-gather decision during a trace."""
    import contextlib

    @contextlib.contextmanager
    def _cm():
        tok = _MXU_OVERRIDE.set(value)
        try:
            yield
        finally:
            _MXU_OVERRIDE.reset(tok)

    return _cm()


def _mxu_gather() -> bool:
    """Whether per-row byte gathers/scatters reformulate as one-hot bf16
    matmuls. XLA-TPU lowers take_along_axis/scatter on [N, W] matrices to
    the scalar core (~49 ms for u8[81920, 56] in an earlier v5e profile,
    against 0.27 ms for the identical one-hot contraction on the MXU; not
    re-measured on this machine). Byte values (< 256) are exact in bf16 and
    exactly one one-hot term fires per output element, so the rewrite is
    bit-exact. CPU keeps the native gather (the matmul costs W x more
    compute there). TUPLEX_MXU_GATHER=0/1 overrides."""
    import os

    ov = _MXU_OVERRIDE.get()
    if ov is not None:
        return ov
    mode = os.environ.get("TUPLEX_MXU_GATHER", "auto")
    if mode in ("0", "1"):
        return mode == "1"
    from ..runtime.jaxcfg import jax

    return jax.default_backend() != "cpu"


# contraction chunk for the one-hot rewrites: bounds the materialized
# one-hot slab at N x Wout x 128 whatever the matrix width (an unchunked
# [61440, 512, 512] one-hot wedged the flights stage on the v5e — XLA
# declined to fuse it into the dot and tried to materialize ~16 GB)
_OH_CHUNK = 128
_OH_MAX_W = 1024      # beyond this the scalar gather wins back


def take_cols(mat, idx):
    """take_along_axis(mat, idx, axis=1) with a TPU-fast path.

    For u8/bool matrices on accelerator backends the gather becomes a
    one-hot MXU contraction (see _mxu_gather), chunked along the
    contraction dim to bound memory. idx must already be clipped to
    [0, W) — same contract as every call site's jnp.clip."""
    w = mat.shape[1]
    if mat.dtype in (jnp.uint8, jnp.bool_) and w <= _OH_MAX_W \
            and _mxu_gather():
        acc = None
        for k0 in range(0, w, _OH_CHUNK):
            k1 = min(k0 + _OH_CHUNK, w)
            oh = idx[:, :, None] == jnp.arange(k0, k1,
                                               dtype=jnp.int32)[None, None, :]
            part = jnp.einsum("njk,nk->nj", oh.astype(jnp.bfloat16),
                              mat[:, k0:k1].astype(jnp.bfloat16),
                              preferred_element_type=jnp.float32)
            acc = part if acc is None else acc + part
        return acc.astype(mat.dtype)
    return jnp.take_along_axis(mat, idx, axis=1)


def table_lookup(table, idx):
    """table[idx] for a small (<=256-entry) u8/bool/small-int table and u8
    indices of any shape — the byte-classification primitive (class
    membership, digit values). The element gather runs on the TPU scalar
    core; the one-hot contraction against the table runs on the MXU and is
    exact for values < 256."""
    table = jnp.asarray(table)
    t = table.shape[0]
    if (table.dtype in (jnp.uint8, jnp.bool_, jnp.int8)
            and t <= 256 and _mxu_gather()):
        flat = idx.reshape(-1, idx.shape[-1]) if idx.ndim > 1 \
            else idx.reshape(1, -1)
        oh = flat[:, :, None] == jnp.arange(t, dtype=flat.dtype)[None, None, :]
        out = jnp.einsum("nkt,t->nk", oh.astype(jnp.bfloat16),
                         table.astype(jnp.bfloat16),
                         preferred_element_type=jnp.float32)
        return out.astype(table.dtype).reshape(idx.shape)
    return jnp.take(table, idx)


# ---------------------------------------------------------------------------
# search
# ---------------------------------------------------------------------------

def find_const(bytes_, lens, needle: str, start=None, reverse: bool = False):
    """str.find / str.rfind with a constant needle. Returns int32 [N], -1 if
    absent. Empty needle matches at `start` (Python semantics: ''.find -> 0)."""
    n, w = bytes_.shape
    nb = const_bytes(needle)
    m = len(nb)
    if m == 0:
        if reverse:
            return lens.astype(jnp.int32)  # s.rfind('') == len(s)
        base = jnp.zeros(n, dtype=jnp.int32) if start is None else start
        return jnp.where(base > lens, -1, base).astype(jnp.int32)
    if m > w:
        return jnp.full(n, -1, dtype=jnp.int32)
    # match[i, p] = bytes[i, p:p+m] == needle, for p in [0, w-m]
    npos = w - m + 1
    match = jnp.ones((n, npos), dtype=bool)
    for j in range(m):  # m is a compile-time constant: unrolled, XLA fuses
        match = match & (bytes_[:, j : j + npos] == nb[j])
    pos = jnp.arange(npos, dtype=jnp.int32)[None, :]
    inside = pos + m <= lens[:, None]
    match = match & inside
    if start is not None:
        # Python semantics: negative start counts from the end
        nstart = jnp.where(start < 0, jnp.maximum(start + lens, 0), start)
        match = match & (pos >= nstart[:, None])
    if reverse:
        found = jnp.max(jnp.where(match, pos, -1), axis=1)
    else:
        big = npos + 1
        first = jnp.min(jnp.where(match, pos, big), axis=1)
        found = jnp.where(first >= big, -1, first)
    return found.astype(jnp.int32)


def contains_const(bytes_, lens, needle: str):
    return find_const(bytes_, lens, needle) >= 0


def startswith_const(bytes_, lens, prefix: str):
    nb = const_bytes(prefix)
    m = len(nb)
    n, w = bytes_.shape
    if m == 0:
        return jnp.ones(n, dtype=bool)
    if m > w:
        return jnp.zeros(n, dtype=bool)
    ok = lens >= m
    for j in range(m):
        ok = ok & (bytes_[:, j] == nb[j])
    return ok


def endswith_const(bytes_, lens, suffix: str):
    nb = const_bytes(suffix)
    m = len(nb)
    n, w = bytes_.shape
    if m == 0:
        return jnp.ones(n, dtype=bool)
    if m > w:
        return jnp.zeros(n, dtype=bool)
    ok = lens >= m
    start = lens - m
    idx = start[:, None] + jnp.arange(m, dtype=jnp.int32)[None, :]
    idx = jnp.clip(idx, 0, w - 1)
    got = take_cols(bytes_, idx)
    ok = ok & jnp.all(got == jnp.asarray(nb)[None, :], axis=1)
    return ok


# ---------------------------------------------------------------------------
# slicing / substring
# ---------------------------------------------------------------------------

def normalize_index(idx, lens):
    """Python index semantics: negatives count from the end."""
    return jnp.where(idx < 0, idx + lens, idx)


def slice_(bytes_, lens, start, stop, out_width: int | None = None):
    """s[start:stop] with per-row dynamic bounds (already normalized, may be
    None for defaults). Returns (bytes [N, Wout], lens [N]).

    Prefix slices (`s[:x]`, start=None) skip the per-row gather entirely —
    the bytes don't move, only the length shrinks. XLA-CPU lowers
    take_along_axis to a scalar row loop, so this one special case removes
    the dominant cost of the zillow extract kernels (`val[:max_idx]`)."""
    n, w = bytes_.shape
    zeros = jnp.zeros(n, dtype=jnp.int32)
    if stop is None:
        stop = lens
    stop = jnp.clip(jnp.where(stop < 0, stop + lens, stop), 0, lens)
    wout = w if out_width is None else out_width
    cols = jnp.arange(wout, dtype=jnp.int32)[None, :]
    if start is None:
        out_len = stop
        src = bytes_[:, :wout] if wout <= w else \
            jnp.pad(bytes_, ((0, 0), (0, wout - w)))
        keep = cols < out_len[:, None]
        return (jnp.where(keep, src, 0).astype(jnp.uint8),
                out_len.astype(jnp.int32))
    start = jnp.clip(jnp.where(start < 0, start + lens, start), 0, lens)
    out_len = jnp.maximum(stop - start, 0)
    idx = start[:, None] + cols
    idx_c = jnp.clip(idx, 0, w - 1)
    out = take_cols(bytes_, idx_c)
    keep = cols < out_len[:, None]
    return jnp.where(keep, out, 0).astype(jnp.uint8), out_len.astype(jnp.int32)


def char_at(bytes_, lens, idx):
    """s[i] -> (bytes [N,1], len [N]=1, err_oob [N] bool)."""
    n, w = bytes_.shape
    nidx = normalize_index(idx, lens)
    oob = (nidx < 0) | (nidx >= lens)
    safe = jnp.clip(nidx, 0, w - 1)
    ch = take_cols(bytes_, safe[:, None])
    return ch.astype(jnp.uint8), jnp.ones(n, dtype=jnp.int32), oob


# ---------------------------------------------------------------------------
# case / strip / replace / concat
# ---------------------------------------------------------------------------

def lower(bytes_, lens):
    is_up = (bytes_ >= 65) & (bytes_ <= 90)
    return jnp.where(is_up, bytes_ + 32, bytes_).astype(jnp.uint8), lens


def upper(bytes_, lens):
    is_lo = (bytes_ >= 97) & (bytes_ <= 122)
    return jnp.where(is_lo, bytes_ - 32, bytes_).astype(jnp.uint8), lens


def swapcase(bytes_, lens):
    is_up = (bytes_ >= 65) & (bytes_ <= 90)
    is_lo = (bytes_ >= 97) & (bytes_ <= 122)
    out = jnp.where(is_up, bytes_ + 32, jnp.where(is_lo, bytes_ - 32, bytes_))
    return out.astype(jnp.uint8), lens


_WHITESPACE = np.array([9, 10, 11, 12, 13, 32], dtype=np.uint8)


def _is_space(bytes_):
    acc = jnp.zeros(bytes_.shape, dtype=bool)
    for c in _WHITESPACE:
        acc = acc | (bytes_ == c)
    return acc


def _is_in_charset(bytes_, chars: str):
    cs = const_bytes(chars)
    acc = jnp.zeros(bytes_.shape, dtype=bool)
    for c in cs:
        acc = acc | (bytes_ == c)
    return acc


def strip(bytes_, lens, chars: str | None = None, left=True, right=True):
    n, w = bytes_.shape
    strippable = _is_space(bytes_) if chars is None else _is_in_charset(bytes_, chars)
    pos = jnp.arange(w, dtype=jnp.int32)[None, :]
    inside = pos < lens[:, None]
    keepable = ~strippable & inside
    if left:
        big = w + 1
        first_keep = jnp.min(jnp.where(keepable, pos, big), axis=1)
        start = jnp.where(first_keep >= big, lens, first_keep)
    else:
        start = jnp.zeros(n, dtype=jnp.int32)
    if right:
        last_keep = jnp.max(jnp.where(keepable, pos, -1), axis=1)
        stop = jnp.where(last_keep < 0, start, last_keep + 1)
    else:
        stop = lens
    return slice_(bytes_, lens, start, jnp.maximum(stop, start))


def replace_const(bytes_, lens, old: str, new: str):
    """str.replace with constant old/new.

    Fast paths: len(old)==len(new) (in-place mask) and new=='' (compaction).
    General case grows the width by the worst-case expansion factor.
    """
    ob, nb = const_bytes(old), const_bytes(new)
    m, k = len(ob), len(nb)
    n, w = bytes_.shape
    if m == 0:
        raise NotImplementedError("replace with empty pattern")
    # match starts
    npos = w - m + 1
    if npos <= 0:
        return bytes_, lens
    match = jnp.ones((n, npos), dtype=bool)
    for j in range(m):
        match = match & (bytes_[:, j : j + npos] == ob[j])
    pos = jnp.arange(npos, dtype=jnp.int32)[None, :]
    match = match & (pos + m <= lens[:, None])
    # resolve overlaps with Python's greedy left-to-right scan: a match is
    # real iff no real match starts in the previous m-1 positions. Greedy
    # selection is sequential — scan over columns with vectorized row state.
    if m > 1:
        from ..runtime.jaxcfg import lax

        def step(next_ok, col_match):
            real_col = col_match & (next_ok <= 0)
            next_ok = jnp.where(real_col, m - 1, next_ok - 1)
            return next_ok, real_col

        init = jnp.zeros(n, dtype=jnp.int32)
        _, real_t = lax.scan(step, init, jnp.transpose(match))
        match = jnp.transpose(real_t)
    # output positions: each input byte either copied or consumed; matched
    # start produces k bytes instead of m.
    is_start = jnp.pad(match, ((0, 0), (0, w - npos)))  # [n, w]
    if k == m:
        # same-length replacement: bytes never move — overwrite in place
        out = bytes_
        for j in range(k):
            at_j = jnp.pad(is_start[:, : w - j], ((0, 0), (j, 0)))
            out = jnp.where(at_j, jnp.uint8(nb[j]), out)
        return out.astype(jnp.uint8), lens
    consumed = jnp.zeros((n, w), dtype=bool)
    for j in range(m):
        consumed = consumed | jnp.pad(is_start[:, : w - j], ((0, 0), (j, 0)))
    inside = _pos_mask(w, lens)
    copied = inside & ~consumed
    if k == 0:
        # pure deletion = stable compaction of the kept bytes. A sort of
        # the kept positions + one gather beats the scatter formulation
        # ~3.4x on CPU (XLA-CPU lowers scatter to a scalar row loop).
        key = jnp.where(copied, jnp.arange(w, dtype=jnp.int32)[None, :], w)
        sk = jnp.sort(key, axis=1)
        out = take_cols(bytes_, jnp.clip(sk, 0, w - 1))
        out_len = jnp.sum(copied, axis=1).astype(jnp.int32)
        mask = jnp.arange(w, dtype=jnp.int32)[None, :] < out_len[:, None]
        return jnp.where(mask, out, 0).astype(jnp.uint8), out_len
    # contribution of each input position to output length
    contrib = jnp.where(is_start & inside, k, jnp.where(copied, 1, 0))
    out_start = jnp.cumsum(contrib, axis=1) - contrib  # exclusive prefix
    out_len = jnp.sum(contrib, axis=1).astype(jnp.int32)
    grow = max(1, -(-k // m))  # ceil(k/m) worst-case expansion
    wout = w * grow if k > m else w
    out = jnp.zeros((n, wout), dtype=jnp.uint8)
    # scatter copied bytes
    rows = jnp.arange(n)[:, None]
    tgt = jnp.where(copied, out_start, wout)  # park non-copied at off-end
    out = _scatter_cols(out, rows, tgt, bytes_, wout)
    # scatter replacement bytes
    for j in range(k):
        tgt_j = jnp.where(is_start & inside, out_start + j, wout)
        src = jnp.full((n, w), nb[j], dtype=jnp.uint8)
        out = _scatter_cols(out, rows, tgt_j, src, wout)
    return out, out_len


def _scatter_cols(out, rows, tgt, src, wout):
    """out[rows, tgt] = src where tgt < wout (off-end writes dropped).
    Call sites guarantee distinct in-range targets per row, so on TPU the
    scatter becomes the transposed one-hot MXU contraction (<=1 term per
    output element -> exact; see _mxu_gather)."""
    if out.dtype == jnp.uint8 and wout <= _OH_MAX_W and _mxu_gather():
        k = tgt.shape[1]
        vals = None
        hit = None
        for k0 in range(0, k, _OH_CHUNK):   # chunk the contraction dim
            k1 = min(k0 + _OH_CHUNK, k)
            oh = tgt[:, k0:k1, None] == jnp.arange(
                wout, dtype=jnp.int32)[None, None, :]
            part = jnp.einsum("nkj,nk->nj", oh.astype(jnp.bfloat16),
                              src[:, k0:k1].astype(jnp.bfloat16),
                              preferred_element_type=jnp.float32)
            h = oh.any(axis=1)
            vals = part if vals is None else vals + part
            hit = h if hit is None else (hit | h)
        return jnp.where(hit, vals.astype(out.dtype), out)
    pad_out = jnp.zeros((out.shape[0], wout + 1), dtype=out.dtype)
    pad_out = pad_out.at[:, :wout].set(out)
    tgt_c = jnp.clip(tgt, 0, wout)
    pad_out = pad_out.at[rows, tgt_c].set(src.astype(out.dtype), mode="drop")
    return pad_out[:, :wout]


def concat(a_bytes, a_lens, b_bytes, b_lens):
    n, wa = a_bytes.shape
    _, wb = b_bytes.shape
    wout = wa + wb
    out = jnp.zeros((n, wout), dtype=jnp.uint8)
    out = out.at[:, :wa].set(a_bytes)
    # place b at offset a_lens via gather from b with shifted index
    pos = jnp.arange(wout, dtype=jnp.int32)[None, :]
    b_idx = pos - a_lens[:, None]
    valid_b = (b_idx >= 0) & (b_idx < b_lens[:, None])
    b_gathered = take_cols(b_bytes, jnp.clip(b_idx, 0, wb - 1))
    out = jnp.where(valid_b, b_gathered, out)
    # zero anything past a_lens that isn't b payload (stale a padding)
    inside = (pos < a_lens[:, None]) | valid_b
    out = jnp.where(inside, out, 0)
    return out.astype(jnp.uint8), (a_lens + b_lens).astype(jnp.int32)


# ---------------------------------------------------------------------------
# comparisons
# ---------------------------------------------------------------------------

def _pad_common(a_bytes, b_bytes):
    wa, wb = a_bytes.shape[1], b_bytes.shape[1]
    w = max(wa, wb)
    if wa < w:
        a_bytes = jnp.pad(a_bytes, ((0, 0), (0, w - wa)))
    if wb < w:
        b_bytes = jnp.pad(b_bytes, ((0, 0), (0, w - wb)))
    return a_bytes, b_bytes


def equals(a_bytes, a_lens, b_bytes, b_lens):
    # zero-tail invariant (bytes beyond lens are 0): equal lens + equal
    # bytes over the NARROWER width decide it — a string longer than the
    # narrow side's width fails the length check, and in-width tails are
    # zero on both sides. Comparing x == "-" then reads [N, 1], not the
    # [N, W] the wide side would force.
    w = min(a_bytes.shape[1], b_bytes.shape[1])
    a, b = _pad_common(a_bytes[:, :w], b_bytes[:, :w])
    same = jnp.all(a == b, axis=1)
    return same & (a_lens == b_lens)


def compare_lt(a_bytes, a_lens, b_bytes, b_lens, or_equal: bool = False):
    """Lexicographic a < b (byte-wise, matching Python for ASCII)."""
    a, b = _pad_common(a_bytes, b_bytes)
    w = a.shape[1]
    pos = jnp.arange(w, dtype=jnp.int32)[None, :]
    va = pos < a_lens[:, None]
    vb = pos < b_lens[:, None]
    ab = jnp.where(va, a, 0).astype(jnp.int32)
    bb = jnp.where(vb, b, 0).astype(jnp.int32)
    diff = ab != bb
    big = w + 1
    first = jnp.min(jnp.where(diff, pos, big), axis=1)
    no_diff = first >= big
    fa = take_cols(ab, jnp.clip(first, 0, w - 1)[:, None])[:, 0]
    fb = take_cols(bb, jnp.clip(first, 0, w - 1)[:, None])[:, 0]
    lt = jnp.where(no_diff, a_lens < b_lens, fa < fb)
    if or_equal:
        return lt | (no_diff & (a_lens == b_lens))
    return lt


# ---------------------------------------------------------------------------
# parse / format
# ---------------------------------------------------------------------------

# post-strip width cap for numeric parses: i64 needs <= 20 chars, every
# practically-occurring float literal <= 26; longer rows route (fail-safe)
_PARSE_WIN = 32


def _narrowed_parse(core, bytes_, lens):
    """Run a numeric parse core on a _PARSE_WIN-wide stripped window.

    Instead of materializing a stripped copy (strip = reductions + a
    full-width gather through slice_), locate the non-space span with two
    reductions and gather ONLY the window the core reads. Wide columns
    (regex-group slices come in at the source width, e.g. [N, 96] on the
    logs pipeline) would otherwise waste 3-4x the work in strip +
    validity/digit masks. Rows whose non-space span exceeds the window can
    still be valid CPython numbers ('0'*40 + '7', float('1'+'0'*40)) —
    those ROUTE to the interpreter instead of claiming ValueError."""
    n, w = bytes_.shape
    pos = jnp.arange(w, dtype=jnp.int32)[None, :]
    inside = pos < lens[:, None]
    core_m = inside & ~_is_space(bytes_)
    fs = jnp.min(jnp.where(core_m, pos, w + 1), axis=1)
    ls = jnp.max(jnp.where(core_m, pos, -1), axis=1)
    span = jnp.maximum(ls - fs + 1, 0)      # 0 = empty / all-space
    win = min(w, _PARSE_WIN)
    idx = fs[:, None] + jnp.arange(win, dtype=jnp.int32)[None, :]
    sb = take_cols(bytes_, jnp.clip(idx, 0, w - 1))
    sl = jnp.minimum(span, win)
    sb = jnp.where(jnp.arange(win, dtype=jnp.int32)[None, :] < sl[:, None],
                   sb, 0).astype(jnp.uint8)
    val, bad, route = core(sb, sl)
    long_rows = span > win
    return val, bad & ~long_rows, route | long_rows


def parse_i64(bytes_, lens):
    """int(s) semantics: optional surrounding spaces, optional sign, digits.
    Returns (val int64 [N], bad bool [N], route bool [N]): `bad` rows are
    EXACT CPython ValueErrors (syntactically not an int); `route` rows are
    valid Python ints that don't fit i64 (arbitrary precision territory) and
    must resolve on the interpreter — conflating them would report
    ValueError where CPython succeeds (advisor finding, round 1)."""
    n, w = bytes_.shape
    if w <= _PARSE_WIN:
        return _parse_i64_core(bytes_, lens)
    # wide columns: span-based window extraction (the core is strip-free,
    # so a pre-stripped window just means fs=0 inside the core); routing is
    # on the non-space SPAN, so heavy space padding still parses on-device
    return _narrowed_parse(_parse_i64_core, bytes_, lens)


def _parse_i64_core(sb, sl):
    """Strip-free core: instead of materializing a stripped copy of the
    bytes (full-width gather), locate the non-space span [fs, ls] with two
    reductions and read the <=20-byte digit window straight out of the
    original matrix — measured ~2x the strip+parse formulation on CPU
    (29.5ms -> 15.5ms at 100k x 25)."""
    n, w = sb.shape
    pos = jnp.arange(w, dtype=jnp.int32)[None, :]
    inside = pos < sl[:, None]
    sp = _is_space(sb)
    core_m = inside & ~sp
    fs = jnp.min(jnp.where(core_m, pos, w + 1), axis=1)
    ls = jnp.max(jnp.where(core_m, pos, -1), axis=1)
    empty = ls < 0                      # all spaces / empty string
    # any whitespace strictly inside the span is invalid ("1 2")
    inner_sp = jnp.any(sp & (pos >= fs[:, None]) & (pos <= ls[:, None]),
                       axis=1)
    first = take_cols(sb, jnp.clip(fs, 0, w - 1)[:, None])[:, 0]
    has_sign = (first == 43) | (first == 45)  # + -
    neg = first == 45
    digit_start = fs + jnp.where(has_sign, 1, 0)
    ndigits = ls - digit_start + 1
    # Vectorized positional sum over a GATHERED digit window: i64 holds
    # <= 19 digits, so only the first 20 positions after the sign matter.
    # Every term d * 10^e is exact and partial sums of positive terms never
    # exceed the total, so for in-range values this equals the sequential
    # Horner exactly — in ~6 ops instead of a 20-step dependent chain.
    win = min(w, 20)
    pos_w = digit_start[:, None] + jnp.arange(win, dtype=jnp.int32)[None, :]
    wb = take_cols(sb, jnp.clip(pos_w, 0, w - 1))
    in_zone_w = pos_w <= ls[:, None]
    is_digit_w = (wb >= 48) & (wb <= 57)
    # invalid if: any non-digit inside the digit zone, or no digits at all
    bad = jnp.any(in_zone_w & ~is_digit_w, axis=1) | (ndigits <= 0) \
        | empty | inner_sp
    # digits beyond the window only occur when ndigits > 19, which routes
    dw = jnp.where(in_zone_w, (wb - 48).astype(jnp.int64), 0)
    exp = ndigits[:, None] - 1 - jnp.arange(win, dtype=jnp.int32)[None, :]
    term_ok = in_zone_w & (exp >= 0) & (exp <= 18)
    p10 = jnp.asarray(np.array([10 ** k for k in range(19)],
                               dtype=np.int64))
    val = jnp.sum(jnp.where(term_ok,
                            dw * jnp.take(p10, jnp.clip(exp, 0, 18)), 0),
                  axis=1)
    # 19-digit magnitudes above i64 max would wrap: lexicographic compare
    # against the max literal routes them to the interpreter (advisor
    # finding, round 1). The one representable edge (-2**63) is
    # conservatively routed too.
    if win >= 19:
        lit = jnp.asarray(np.frombuffer(b"9223372036854775807", np.uint8)
                          .astype(np.int64) - 48)
        diff = dw[:, :19] - lit[None, :]
        nz = diff != 0
        first = jnp.argmax(nz, axis=1)
        over19 = nz.any(axis=1) & \
            (take_cols(diff, first[:, None])[:, 0] > 0)
        ovf = (ndigits == 19) & over19
    else:
        ovf = jnp.zeros(n, dtype=jnp.bool_)  # w < 19: no 19-digit values
    # CPython accepts grammar outside this kernel: PEP 515 underscores
    # ("1_0" == 10) and non-ASCII digits/whitespace (int("١٢"),
    # "\xa012\xa0"). Those rows ROUTE to the interpreter — claiming
    # ValueError would silently drop rows CPython converts.
    outside = jnp.any(inside & ((sb == 95) | (sb >= 128)), axis=1)
    bad = bad & ~outside
    route = (ovf | (ndigits > 19) | outside) & ~bad
    val = jnp.where(neg, -val, val)
    # materialize: the Horner chain must not be re-inlined (and per-element
    # recomputed) into every downstream consumer fusion
    return lax.optimization_barrier((val, bad, route))


def parse_f64(bytes_, lens):
    """float(s): [sign] digits [.digits] [e[sign]digits].
    Returns (val f64 [N], bad bool [N], route bool [N]): `bad` rows are
    EXACT CPython ValueErrors; `route` rows are inf/infinity/nan literals
    (CPython accepts them, this kernel doesn't evaluate them) and must
    resolve on the interpreter."""
    return _narrowed_parse(_parse_f64_core, bytes_, lens)


_MAXP = 63      # widest mantissa the f64 power table weighs exactly


def _parse_f64_core(sb, sl):
    n, w = sb.shape
    pos = jnp.arange(w, dtype=jnp.int32)[None, :]
    inside = pos < sl[:, None]
    is_digit = (sb >= 48) & (sb <= 57)
    dot = sb == 46
    e_chr = (sb == 101) | (sb == 69)
    sign = (sb == 43) | (sb == 45)
    big = w + 1
    # landmark positions
    dot_pos = jnp.min(jnp.where(dot & inside, pos, big), axis=1)
    e_pos = jnp.min(jnp.where(e_chr & inside, pos, big), axis=1)
    has_dot = dot_pos < big
    has_e = e_pos < big
    mant_end = jnp.where(has_e, e_pos, sl)
    first = sb[:, 0] if w > 0 else jnp.zeros(n, dtype=jnp.uint8)
    lead_sign = (first == 43) | (first == 45)
    neg = first == 45
    int_start = jnp.where(lead_sign, 1, 0)
    int_end = jnp.where(has_dot & (dot_pos < mant_end), dot_pos, mant_end)
    frac_start = jnp.where(has_dot, dot_pos + 1, mant_end)
    # validity: every char inside must be digit / single dot / single e / sign
    # in legal spot
    ok_char = is_digit | (dot & (pos == dot_pos[:, None])) | \
        (e_chr & (pos == e_pos[:, None])) | \
        (sign & ((pos == 0) | (pos == (e_pos + 1)[:, None])))
    bad = jnp.any(inside & ~ok_char, axis=1)
    n_int = int_end - int_start
    n_frac = jnp.where(has_dot, mant_end - frac_start, 0)
    bad = bad | ((n_int <= 0) & (n_frac <= 0)) | (sl <= 0)
    bad = bad | (has_e & (has_dot & (dot_pos > e_pos)))
    d = jnp.where(is_digit, (sb - 48).astype(jnp.float64), 0.0)
    # mantissa via a rank-based positional sum (replaces a w-step dependent
    # Horner chain — hundreds of sequential ops for wide columns). Each
    # digit's weight is 10^(n_mant - rank); for <= 15-16 digit mantissas
    # every term and partial sum is an exact f64 integer, identical to
    # Horner; beyond that both are approximations (see the fast-path note
    # below).
    in_mant = (pos >= int_start[:, None]) & (pos < mant_end[:, None]) & \
        inside & is_digit
    rank = jnp.cumsum(in_mant.astype(jnp.int32), axis=1)  # 1-based in-mask
    n_mant = rank[:, -1] if w else jnp.zeros(n, dtype=jnp.int32)
    m_exp = n_mant[:, None] - rank
    # exact powers via lookup below 2^53's reach; huge mantissas clamp (the
    # value overflows f64 integer precision there regardless)
    p10f = jnp.asarray(np.array([10.0 ** k for k in range(_MAXP + 1)],
                                dtype=np.float64))
    mant = jnp.sum(jnp.where(in_mant,
                             d * jnp.take(p10f, jnp.clip(m_exp, 0, _MAXP)),
                             0.0), axis=1)
    scale = jnp.where(has_dot, (mant_end - frac_start).astype(jnp.float64), 0.0)
    # exponent digits: same rank trick (exponents are tiny integers, exact)
    exp_sign_pos = e_pos + 1
    exp_first = take_cols(sb, jnp.clip(exp_sign_pos, 0, w - 1)[:, None])[:, 0]
    exp_has_sign = has_e & ((exp_first == 43) | (exp_first == 45))
    exp_neg = has_e & (exp_first == 45)
    exp_start = jnp.where(exp_has_sign, e_pos + 2, e_pos + 1)
    in_exp = has_e[:, None] & (pos >= exp_start[:, None]) & inside & is_digit
    erank = jnp.cumsum(in_exp.astype(jnp.int32), axis=1)
    e_ndig = erank[:, -1] if w else jnp.zeros(n, dtype=jnp.int32)
    e_exp = e_ndig[:, None] - erank
    exp_val = jnp.sum(jnp.where(in_exp,
                                d * jnp.take(p10f,
                                             jnp.clip(e_exp, 0, _MAXP)),
                                0.0), axis=1)
    n_exp_digits = jnp.where(has_e, sl - exp_start, 1)
    bad = bad | (has_e & (n_exp_digits <= 0))
    exp_val = jnp.where(exp_neg, -exp_val, exp_val)
    if f64_is_f32_pair():
        # no IEEE binary64 on this device: one f64 divide is NOT strtod
        # there ("0.05" came out as 0.04999999999999982 on the v5e and
        # TPC-H Q6's `0.05 <= discount` lost a third of its rows). What
        # the integer conversion cannot do ROUTES instead of coming back
        # nearly right.
        val, exact = _decimal_f32_pair(d, in_mant, m_exp, exp_val - scale)
        exact = exact & (n_mant <= _DEC_MAX_DIGITS)
    else:
        # correctly-rounded decimal->binary for the common case: the
        # integer mantissa is exact (< 2^53) and 10^|e| is exact for
        # |e| <= 22, so ONE f64 multiply or divide yields the same bits as
        # CPython's strtod (the classic Gay fast path). |e| > 22 falls
        # back to powers (rare in data files; tiny ulp error possible
        # there).
        e = exp_val - scale
        small = jnp.abs(e) <= 22.0
        # exact powers of ten via lookup (jnp.power lowers to exp*log and
        # is NOT exact even for integer exponents)
        p10 = jnp.asarray(np.array([10.0 ** k for k in range(23)],
                                   dtype=np.float64))
        abs_e = jnp.clip(jnp.abs(e), 0.0, 22.0).astype(jnp.int32)
        pow_abs = jnp.take(p10, abs_e)
        val_small = jnp.where(e >= 0, mant * pow_abs, mant / pow_abs)
        # 0 * inf = NaN for zero mantissas with overflowing exponents
        # ('0e400' is 0.0 in CPython): pin the zero-mantissa case
        val_big = jnp.where(mant == 0.0, 0.0, mant * jnp.power(10.0, e))
        val = jnp.where(small, val_small, val_big)
        # mantissas spanning more digits than the power table ROUTE — the
        # clamped weights would silently shrink the value (review finding:
        # '1'+'0'*69 parsed to 1e63)
        exact = n_mant <= _MAXP + 1
    val = jnp.where(neg, -val, val)

    # float('inf') / 'Infinity' / 'nan' (any case, optional sign) are valid
    # CPython floats outside this kernel's grammar: route, don't ValueError
    def _word_at(word):
        if w == 0:
            return jnp.zeros(n, dtype=jnp.bool_)
        L = len(word)
        idxs = int_start[:, None] + jnp.arange(L, dtype=jnp.int32)[None, :]
        ch = take_cols(sb, jnp.clip(idxs, 0, w - 1))
        m = (sl - int_start) == L
        for j, c in enumerate(word):
            m = m & ((ch[:, j] | 32) == ord(c))
        return m

    # PEP 515 underscores and non-ASCII digits/whitespace are valid CPython
    # float grammar this kernel doesn't evaluate: route, don't ValueError
    outside = jnp.any(inside & ((sb == 95) | (sb >= 128)), axis=1)
    route = _word_at("inf") | _word_at("infinity") | _word_at("nan") | \
        outside | ~exact
    bad = bad & ~route
    return lax.optimization_barrier((val, bad, route))


# --- exact decimal -> binary64 in integer arithmetic (f32-pair devices) ----

_DEC_MAX_K = 27                         # 5^27 < 2^63
_DEC_MAX_DIGITS = 18                    # 10^18 < 2^63
_P5 = np.array([5 ** k for k in range(_DEC_MAX_K + 1)], dtype=np.uint64)
_P5_MAXM = np.array([(2 ** 63 - 1) // 5 ** k for k in range(_DEC_MAX_K + 1)],
                    dtype=np.uint64)
_P2 = np.array([2 ** k for k in range(64)], dtype=np.uint64)
_P10U = np.array([10 ** k for k in range(_DEC_MAX_DIGITS)], dtype=np.uint64)


def _bitlen64(v):
    """Bit length of u64 [N] (0 for 0), from 32-bit clz: 64-bit integers
    are pairs of 32-bit words on a TPU."""
    hi = (v >> jnp.uint64(32)).astype(jnp.uint32)
    lo = v.astype(jnp.uint32)
    return jnp.where(hi != 0, 64 - lax.clz(hi).astype(jnp.int32),
                     32 - lax.clz(lo).astype(jnp.int32))


def decimal_to_binary53(m, e10):
    """m * 10^e10 correctly rounded (half-even) to 53 significant bits, in
    exact integer arithmetic: returns (M u64 [N], E i32 [N], ok bool [N])
    with the rounded value M * 2^E and 2^52 <= M <= 2^53 (M = 0 for m = 0).
    ``ok`` is False where the conversion would leave 64 bits (m >= 2^63,
    |e10| > 27, m * 5^e10 >= 2^63): those rows' M/E are meaningless.

    m * 10^e = (m * 5^e) * 2^e for e >= 0 and (m / 5^-e) * 2^e below: one
    bit-serial long division of 63-bit-normalized operands yields 56
    quotient bits and an exact sticky, which is all rounding needs."""
    m = m.astype(jnp.uint64)
    k = jnp.clip(jnp.abs(e10), 0, _DEC_MAX_K)
    p5 = jnp.take(jnp.asarray(_P5), k)
    pos = e10 >= 0
    ok = (jnp.abs(e10) <= _DEC_MAX_K) & (m < jnp.uint64(1 << 63)) & \
        (~pos | (m <= jnp.take(jnp.asarray(_P5_MAXM), k)))
    num = jnp.where(pos, m * p5, m)
    num = jnp.where(ok, num, jnp.uint64(1))
    den = jnp.where(pos | ~ok, jnp.uint64(1), p5)
    ln = _bitlen64(num)
    ld = _bitlen64(den)
    p2 = jnp.asarray(_P2)
    # top bit at position 62: rem < 2*dn < 2^64 holds through the loop
    t = num * jnp.take(p2, jnp.clip(63 - ln, 0, 63))
    dn = den * jnp.take(p2, jnp.clip(63 - ld, 0, 63))

    def step(_, qr):
        q, rem = qr
        ge = rem >= dn
        q = (q << jnp.uint64(1)) | ge.astype(jnp.uint64)
        rem = jnp.where(ge, rem - dn, rem) << jnp.uint64(1)
        return q, rem

    q, rem = lax.fori_loop(0, 56, step, (jnp.zeros_like(t), t))
    sticky = rem != 0
    # q = floor(t/dn * 2^55) has 55 or 56 bits: drop 2 or 3, half-even
    wide = q >= jnp.uint64(1 << 55)
    M = jnp.where(wide, q >> jnp.uint64(3), q >> jnp.uint64(2))
    rest = jnp.where(wide, q & jnp.uint64(7), (q & jnp.uint64(3)) << 1)
    up = (rest > 4) | ((rest == 4) & (sticky | ((M & jnp.uint64(1)) == 1)))
    M = M + up.astype(jnp.uint64)
    E = ln - ld - 55 + jnp.where(wide, 3, 2) + e10
    zero = m == 0
    return jnp.where(zero, jnp.uint64(0), M), jnp.where(zero, 0, E), ok


def binary53_to_f32_pair(M, E):
    """The (hi, lo) float32 pair a TPU holds for the binary64 M * 2^E, as
    the host-to-device conversion makes it: hi = RN24(x), lo = RN24(x-hi).
    Returns (hi f32, lo f32, ok): ok is False where hi or lo would leave
    float32's normal range (the device cannot hold that value)."""
    M = M.astype(jnp.uint64)
    H = M >> jnp.uint64(29)
    low = M & jnp.uint64((1 << 29) - 1)
    half = jnp.uint64(1 << 28)
    H = H + ((low > half) | ((low == half) & ((H & jnp.uint64(1)) == 1))
             ).astype(jnp.uint64)
    R = M.astype(jnp.int64) - (H << jnp.uint64(29)).astype(jnp.int64)
    ok = (M == 0) | ((E >= -126) & (E <= 74))
    Ec = jnp.clip(E, -126, 74)

    def pow2(e):
        return lax.bitcast_convert_type((e + 127) << 23, jnp.float32)

    hi = H.astype(jnp.int32).astype(jnp.float32) * pow2(Ec + 29)
    lo = R.astype(jnp.int32).astype(jnp.float32) * pow2(Ec)
    return hi, lo, ok


def _decimal_f32_pair(d, in_mant, m_exp, e10):
    """parse_f64's value on a device whose float64 is a float32 pair, from
    the digit values `d` [N, w], the mantissa mask and per-digit decimal
    weights, and the decimal exponent (f64, integral): (val f64 [N],
    exact bool [N]). Inexact rows (|exponent| > 27, a mantissa or product
    past 2^63, a value outside float32's range) carry garbage."""
    m = jnp.sum(jnp.where(in_mant,
                          d.astype(jnp.uint64) * jnp.take(
                              jnp.asarray(_P10U),
                              jnp.clip(m_exp, 0, _DEC_MAX_DIGITS - 1)),
                          jnp.uint64(0)), axis=1)
    M, E, ok = decimal_to_binary53(
        m, jnp.clip(e10, -4096.0, 4096.0).astype(jnp.int32))
    hi, lo, fits = binary53_to_f32_pair(M, E)
    return hi.astype(jnp.float64) + lo.astype(jnp.float64), ok & fits


_I64_MAX_DIGITS = 20  # sign + 19 digits


def format_i64(vals, width: int = 0, pad_zero: bool = False):
    """str(i) / '%0Nd' % i -> (bytes [N, W], lens [N])."""
    n = vals.shape[0]
    w = max(_I64_MAX_DIGITS, width)
    neg = vals < 0
    # careful: abs(i64 min) overflows; data pipelines don't hit it — clamp
    mag = jnp.where(neg, -vals, vals).astype(jnp.uint64)
    # right-aligned digits in ONE broadcast divide: digit j = mag // 10^k
    # % 10 (the old per-digit loop was ~60 sequential div/mod/scatter ops —
    # a measurable slice of the stage graph and of its compile time)
    wd = min(w, _I64_MAX_DIGITS)  # uint64 has <= 20 decimal digits
    p10 = jnp.asarray(
        np.array([10 ** k for k in range(wd - 1, -1, -1)], dtype=np.uint64))
    digits = ((mag[:, None] // p10[None, :]) % 10).astype(jnp.uint8) + 48
    if w > wd:  # width request beyond any uint64: left-fill with '0's
        digits = jnp.concatenate(
            [jnp.full((n, w - wd), 48, dtype=jnp.uint8), digits], axis=1)
    ndig = jnp.maximum(
        w - jnp.sum(jnp.cumsum(digits != 48, axis=1) == 0, axis=1), 1
    ).astype(jnp.int32)
    if pad_zero and width > 0:
        ndig = jnp.maximum(ndig, width - jnp.where(neg, 1, 0))
    out_len = ndig + jnp.where(neg, 1, 0)
    # build output: optional '-', then the last `ndig` digits
    pos = jnp.arange(w + 1, dtype=jnp.int32)[None, :]
    digit_idx = pos - jnp.where(neg, 1, 0)[:, None] + (w - ndig)[:, None]
    gathered = take_cols(jnp.pad(digits, ((0, 0), (0, 1))),
                         jnp.clip(digit_idx, 0, w))
    out = jnp.where(
        (pos == 0) & neg[:, None], 45, gathered
    )
    inside = pos < out_len[:, None]
    out = jnp.where(inside, out, 0)
    # materialize: the digit-division chain must not re-inline into every
    # downstream consumer (1D consumers like lengths otherwise recompute
    # the whole [N, W] loop per element)
    return lax.optimization_barrier(
        (out.astype(jnp.uint8), out_len.astype(jnp.int32)))


def from_numpy_strings(values: list[str | None]):
    """Host helper for tests."""
    enc = [(v.encode() if v is not None else b"") for v in values]
    w = max((len(b) for b in enc), default=1) or 1
    mat = np.zeros((len(enc), w), dtype=np.uint8)
    lens = np.zeros(len(enc), dtype=np.int32)
    for i, b in enumerate(enc):
        mat[i, : len(b)] = np.frombuffer(b, np.uint8)
        lens[i] = len(b)
    return jnp.asarray(mat), jnp.asarray(lens)


def to_python_strings(bytes_, lens) -> list[str]:
    b = np.asarray(bytes_)
    l = np.asarray(lens)
    return [bytes(b[i, : l[i]]).decode("utf-8", errors="replace")
            for i in range(b.shape[0])]


# ---------------------------------------------------------------------------
# counting / char classes / casing extras
# ---------------------------------------------------------------------------

def count_const(bytes_, lens, needle: str):
    """str.count with constant needle (non-overlapping, Python semantics)."""
    n, w = bytes_.shape
    nb = const_bytes(needle)
    m = len(nb)
    if m == 0:
        return (lens + 1).astype(jnp.int64)
    if m > w:
        return jnp.zeros(n, dtype=jnp.int64)
    npos = w - m + 1
    match = jnp.ones((n, npos), dtype=bool)
    for j in range(m):
        match = match & (bytes_[:, j : j + npos] == nb[j])
    pos = jnp.arange(npos, dtype=jnp.int32)[None, :]
    match = match & (pos + m <= lens[:, None])
    if m > 1:
        from ..runtime.jaxcfg import lax

        def step(next_ok, col_match):
            real_col = col_match & (next_ok <= 0)
            next_ok = jnp.where(real_col, m - 1, next_ok - 1)
            return next_ok, real_col

        _, real_t = lax.scan(step, jnp.zeros(n, dtype=jnp.int32),
                             jnp.transpose(match))
        match = jnp.transpose(real_t)
    return jnp.sum(match, axis=1).astype(jnp.int64)


def char_class_all(bytes_, lens, kind: str):
    """isdigit/isdecimal/isnumeric/isalpha/isalnum/isspace — ASCII
    semantics (the caller's ascii guard routes multibyte rows), all chars
    in class AND non-empty."""
    is_digit = (bytes_ >= 48) & (bytes_ <= 57)
    is_alpha = ((bytes_ >= 65) & (bytes_ <= 90)) | \
        ((bytes_ >= 97) & (bytes_ <= 122))
    if kind in ("isdigit", "isdecimal", "isnumeric"):
        cls = is_digit     # identical over ASCII
    elif kind == "isalpha":
        cls = is_alpha
    elif kind == "isalnum":
        cls = is_digit | is_alpha
    elif kind == "isspace":
        cls = _is_space(bytes_)
    else:
        raise ValueError(kind)
    inside = _pos_mask(bytes_.shape[1], lens)
    return jnp.all(cls | ~inside, axis=1) & (lens > 0)


def case_pred(bytes_, lens, kind: str):
    """islower/isupper/istitle — ASCII semantics (ascii-guarded callers).

    python: islower = at least one cased char and no uppercase; isupper
    symmetric; istitle = at least one cased char, uppercase only at the
    start of cased runs, lowercase only inside them."""
    inside = _pos_mask(bytes_.shape[1], lens)
    up = (bytes_ >= 65) & (bytes_ <= 90) & inside
    lo = (bytes_ >= 97) & (bytes_ <= 122) & inside
    cased = up | lo
    has_cased = jnp.any(cased, axis=1)
    if kind == "islower":
        return has_cased & ~jnp.any(up, axis=1)
    if kind == "isupper":
        return has_cased & ~jnp.any(lo, axis=1)
    if kind == "istitle":
        prev_cased = jnp.pad(cased[:, :-1], ((0, 0), (1, 0)))
        bad = (up & prev_cased) | (lo & ~prev_cased)
        return has_cased & ~jnp.any(bad, axis=1)
    raise ValueError(kind)


def capitalize(bytes_, lens):
    """First char upper, rest lower."""
    lb, ll = lower(bytes_, lens)
    first = lb[:, 0:1]
    is_lo = (first >= 97) & (first <= 122)
    ub = jnp.where(is_lo, first - 32, first)
    out = jnp.concatenate([ub, lb[:, 1:]], axis=1)
    return out.astype(jnp.uint8), ll


def title(bytes_, lens):
    """str.title: uppercase letters starting a word (after non-alpha)."""
    n, w = bytes_.shape
    is_alpha = ((bytes_ >= 65) & (bytes_ <= 90)) | \
        ((bytes_ >= 97) & (bytes_ <= 122))
    prev_alpha = jnp.pad(is_alpha[:, :-1], ((0, 0), (1, 0)))
    starts = is_alpha & ~prev_alpha
    lb, _ = lower(bytes_, lens)
    ub, _ = upper(bytes_, lens)
    return jnp.where(starts, ub, lb).astype(jnp.uint8), lens


def zfill(bytes_, lens, width: int):
    """str.zfill(width): left-pad digits with '0' after any sign."""
    n, w = bytes_.shape
    wout = max(w, width)
    first = bytes_[:, 0] if w else jnp.zeros(n, jnp.uint8)
    has_sign = ((first == 43) | (first == 45)) & (lens > 0)
    out_len = jnp.maximum(lens, width)
    nzeros = out_len - lens
    pos = jnp.arange(wout, dtype=jnp.int32)[None, :]
    sign_col = (pos == 0) & has_sign[:, None]
    # source index into original string for each output position
    body_start = jnp.where(has_sign, 1, 0)
    src_idx = pos - nzeros[:, None]
    src_idx = jnp.where(sign_col, 0, jnp.where(
        pos < (body_start + nzeros)[:, None], -1, src_idx))
    is_zero = (src_idx < 0) & ~sign_col & (pos < out_len[:, None])
    gathered = take_cols(jnp.pad(bytes_, ((0, 0), (0, max(0, wout - w + 1)))),
        jnp.clip(src_idx, 0, w))[:, :wout]
    out = jnp.where(sign_col, first[:, None], jnp.where(is_zero, 48, gathered))
    inside = pos < out_len[:, None]
    out = jnp.where(inside, out, 0)
    return out.astype(jnp.uint8), out_len.astype(jnp.int32)


def pad_left(bytes_, lens, width: int, fillchar: str = " "):
    """Right-align into a field of `width` (str.rjust / '%Nd' space pad)."""
    n, w = bytes_.shape
    wout = max(w, width)
    fill = const_bytes(fillchar)[0]
    out_len = jnp.maximum(lens, width)
    shift = out_len - lens
    pos = jnp.arange(wout, dtype=jnp.int32)[None, :]
    src_idx = pos - shift[:, None]
    in_pad = (src_idx < 0) & (pos < out_len[:, None])
    padded_src = jnp.pad(bytes_, ((0, 0), (0, max(0, wout - w + 1))))
    gathered = take_cols(padded_src, jnp.clip(src_idx, 0, w))[:, :wout]
    out = jnp.where(in_pad, fill, gathered)
    inside = pos < out_len[:, None]
    return jnp.where(inside, out, 0).astype(jnp.uint8), out_len.astype(jnp.int32)


def non_ascii_rows(bytes_, lens):
    """[N] bool — rows containing any non-ASCII byte inside their length.
    Index-space string ops (len, find, slicing) operate on UTF-8 BYTES; for
    multibyte rows that diverges from Python's codepoint semantics, so those
    rows must take the interpreter path (normal-case violation)."""
    inside = _pos_mask(bytes_.shape[1], lens)
    return jnp.any(inside & (bytes_ >= 128), axis=1)


def capwords(bytes_, lens):
    """string.capwords(s): split on whitespace, capitalize each word, join
    with single spaces (collapses runs + strips ends)."""
    n, w = bytes_.shape
    pos = jnp.arange(w, dtype=jnp.int32)[None, :]
    inside = pos < lens[:, None]
    ws = _is_space(bytes_) & inside
    nonws = ~ws & inside
    # capitalize: lower everything, upper at word starts
    prev_nonws = jnp.pad(nonws[:, :-1], ((0, 0), (1, 0)))
    word_start = nonws & ~prev_nonws
    lb, _ = lower(bytes_, lens)
    is_lo = (lb >= 97) & (lb <= 122)
    cased = jnp.where(word_start & is_lo, lb - 32, lb)
    # keep: all non-ws bytes, plus ONE space between words (a ws byte whose
    # previous kept char is non-ws and which has a non-ws later)
    nonws_after = jnp.flip(jnp.cumsum(jnp.flip(nonws, 1), axis=1), 1) - nonws
    sep = ws & prev_nonws & (nonws_after > 0)
    keep = nonws | sep
    out_char = jnp.where(sep, 32, cased)
    contrib = keep.astype(jnp.int32)
    out_start = jnp.cumsum(contrib, axis=1) - contrib
    out_len = jnp.sum(contrib, axis=1).astype(jnp.int32)
    out = jnp.zeros((n, w), dtype=jnp.uint8)
    rows = jnp.arange(n)[:, None]
    tgt = jnp.where(keep, out_start, w)
    out = _scatter_cols(out, rows, tgt, out_char, w)
    return out.astype(jnp.uint8), out_len


def pad_right(bytes_, lens, width: int, fillchar: str = " "):
    """Left-align into a field of `width` (str.ljust / '{:5}' on strings)."""
    n, w = bytes_.shape
    wout = max(w, width)
    fill = const_bytes(fillchar)[0]
    out_len = jnp.maximum(lens, width)
    if wout > w:
        bytes_ = jnp.pad(bytes_, ((0, 0), (0, wout - w)))
    pos = jnp.arange(wout, dtype=jnp.int32)[None, :]
    in_pad = (pos >= lens[:, None]) & (pos < out_len[:, None])
    out = jnp.where(in_pad, fill, bytes_)
    inside = pos < out_len[:, None]
    return jnp.where(inside, out, 0).astype(jnp.uint8), out_len.astype(jnp.int32)


def center(bytes_, lens, width: int, fillchar: str = " "):
    """str.center(width[, fillchar]) with CPython's left-margin rule
    (marg // 2 + (marg & width & 1))."""
    n, w = bytes_.shape
    wout = max(w, width)
    fill = const_bytes(fillchar)[0]
    marg = jnp.maximum(width - lens, 0)
    left = marg // 2 + (marg & width & 1)
    out_len = jnp.maximum(lens, width)
    pos = jnp.arange(wout, dtype=jnp.int32)[None, :]
    src_idx = pos - left[:, None]
    in_body = (src_idx >= 0) & (src_idx < lens[:, None])
    padded = jnp.pad(bytes_, ((0, 0), (0, max(0, wout - w + 1))))
    gathered = take_cols(padded, jnp.clip(src_idx, 0, w))[:, :wout]
    inside = pos < out_len[:, None]
    out = jnp.where(in_body, gathered, jnp.where(inside, fill, 0))
    return out.astype(jnp.uint8), out_len.astype(jnp.int32)


def _ws_token_marks(bytes_, lens):
    """(starts, nonws) masks for whitespace-separated tokens."""
    inside = _pos_mask(bytes_.shape[1], lens)
    nonws = inside & ~_is_space(bytes_)
    prev = jnp.pad(nonws[:, :-1], ((0, 0), (1, 0)))
    return nonws & ~prev, nonws


def ws_token_count(bytes_, lens):
    """Number of whitespace-separated tokens per row (len(s.split()))."""
    starts, _ = _ws_token_marks(bytes_, lens)
    return jnp.sum(starts, axis=1).astype(jnp.int64)


def ws_token_bounds(bytes_, lens, k: int):
    """(start, stop, missing) of the k-th whitespace-separated token.
    start==w sentinel rows are reported via `missing`."""
    n, w = bytes_.shape
    starts, nonws = _ws_token_marks(bytes_, lens)
    ordn = jnp.cumsum(starts, axis=1)
    pos = jnp.arange(w, dtype=jnp.int32)[None, :]
    cand = jnp.where(starts & (ordn == k + 1), pos, w)
    start = jnp.min(cand, axis=1).astype(jnp.int32)
    missing = start >= w
    after = pos >= start[:, None]
    cand2 = jnp.where(after & ~nonws, pos, w)
    stop = jnp.minimum(jnp.min(cand2, axis=1).astype(jnp.int32), lens)
    return start, stop, missing


def format_f64(vals, prec: int):
    """%.Nf fixed-point rendering (reference: FunctionRegistry float
    formatting; the reference leans on snprintf — here the digits come from
    scaled integer math). Returns (bytes, lens, suspect): `suspect` rows
    (near-tie rounding where binary-vs-decimal double rounding could
    diverge from CPython, |v| >= 1e15, or non-finite) must take the
    interpreter path."""
    scale_i = int(10 ** prec)
    neg = jnp.signbit(vals)        # CPython renders -0.0 as "-0.00"
    a = jnp.abs(vals)
    scaled_f = a * float(scale_i)
    scaled = jnp.rint(scaled_f).astype(jnp.int64)
    frac = scaled_f - jnp.floor(scaled_f)
    # tie window: a few ULPs of the scaled value (the one rounding the
    # scaling multiply can introduce), NOT a relative 1e-9 — that would
    # mark every value past ~5e8 suspect and silently de-compile them
    tie = jnp.abs(frac - 0.5) <= 16 * 2.2e-16 * jnp.maximum(scaled_f, 1.0)
    suspect = tie | (a >= 1e15) | ~jnp.isfinite(vals)
    ip = scaled // scale_i
    ib, il = format_i64(ip)
    if prec > 0:
        fp = scaled % scale_i
        db, dl = broadcast_const(".", vals.shape[0])
        fb, fl = format_i64(fp, width=prec, pad_zero=True)
        ib, il = concat(*concat(ib, il, db, dl), fb, fl)
    sb, sl_full = broadcast_const("-", vals.shape[0])
    sl = jnp.where(neg, sl_full, 0)
    ob, ol = concat(sb, sl, ib, il)
    return ob, ol, suspect


def splice_spans(bytes_, lens, starts, ends, valid, new: str):
    """Delete the (ordered, non-overlapping) spans [starts[:,k], ends[:,k])
    and insert `new` at each — the output assembler for general re.sub
    (emitter._re_sub's NFA match loop finds the spans; reference:
    FunctionRegistry re.sub codegen). starts/ends are [N, K] int32, valid
    [N, K] bool; invalid spans are ignored. Returns (out_bytes, out_lens)
    at width W + K*max(len(new)-1, 0)."""
    n, w = bytes_.shape
    k = starts.shape[1] if starts.ndim == 2 else 0
    nb = const_bytes(new)
    r = len(new.encode("utf-8"))
    wout = w + k * max(r - 1, 0)
    pos = jnp.arange(w, dtype=jnp.int32)[None, :]
    starts = jnp.where(valid, starts, jnp.int32(w + 1))
    ends = jnp.where(valid, ends, jnp.int32(w + 1))
    span_len = jnp.maximum(ends - starts, 0)
    inside = jnp.zeros((n, w), dtype=bool)
    removed_before = jnp.zeros((n, w), dtype=jnp.int32)
    spans_before = jnp.zeros((n, w), dtype=jnp.int32)
    for j in range(k):
        st = starts[:, j][:, None]
        en = ends[:, j][:, None]
        inside = inside | ((pos >= st) & (pos < en))
        past = en <= pos
        removed_before = removed_before + jnp.where(
            past, (en - st)[:, 0][:, None], 0)
        spans_before = spans_before + past.astype(jnp.int32)
    keep = (pos < lens[:, None]) & ~inside
    out_pos = pos - removed_before + r * spans_before
    # per-row scatters (kept bytes land on distinct output slots; insertion
    # slots are disjoint from them by construction) — _scatter_cols picks
    # the MXU one-hot path on TPU, the .at[].set scatter on CPU
    rows2 = jnp.arange(n, dtype=jnp.int32)[:, None]
    tgt = jnp.where(keep, out_pos, wout)
    out = _scatter_cols(jnp.zeros((n, wout), dtype=bytes_.dtype),
                        rows2, tgt, bytes_, wout)
    # replacement copies: span j inserts at st_j - removed(st_j) + r*j
    cum_removed = jnp.cumsum(span_len, axis=1) - span_len   # removed before j
    for j in range(k):
        base = starts[:, j] - cum_removed[:, j] + r * j
        ok = valid[:, j]
        for rr in range(r):
            tgt_c = jnp.where(ok, base + rr, wout)[:, None]
            src = jnp.full((n, 1), nb[rr], dtype=bytes_.dtype)
            out = _scatter_cols(out, rows2, tgt_c, src, wout)
    total_removed = jnp.sum(jnp.where(valid, span_len, 0), axis=1)
    n_spans = jnp.sum(valid.astype(jnp.int32), axis=1)
    out_lens = lens - total_removed + r * n_spans
    return out, out_lens.astype(lens.dtype)


def replace_class_runs(bytes_, lens, table: np.ndarray, new: str):
    """re.sub('[class]+', new, s): each maximal run of class-member bytes
    becomes `new` (reference: FunctionRegistry re.sub codegen; the common
    data-cleaning subset — full regex replacement stays interpreter).
    `table` is a [256] bool membership table."""
    nb = const_bytes(new)
    k = len(nb)
    n, w = bytes_.shape
    inside = _pos_mask(w, lens)
    member = table_lookup(jnp.asarray(table), bytes_.astype(jnp.int32)) & inside
    prev = jnp.pad(member[:, :-1], ((0, 0), (1, 0)))
    run_start = member & ~prev
    copied = inside & ~member
    contrib = jnp.where(run_start, k, jnp.where(copied, 1, 0))
    out_start = jnp.cumsum(contrib, axis=1) - contrib
    out_len = jnp.sum(contrib, axis=1).astype(jnp.int32)
    wout = w * k if k > 1 else max(w, 1)
    rows = jnp.arange(n)[:, None]
    out = jnp.zeros((n, wout), dtype=jnp.uint8)
    tgt = jnp.where(copied, out_start, wout)   # park non-copied off-end
    out = _scatter_cols(out, rows, tgt, bytes_, wout)
    for j in range(k):   # k is a small compile-time constant
        tgt_j = jnp.where(run_start, out_start + j, wout)
        rep = jnp.full((n, w), nb[j], dtype=jnp.uint8)
        out = _scatter_cols(out, rows, tgt_j, rep, wout)
    return out.astype(jnp.uint8), out_len


def group_thousands(bytes_, lens):
    """Insert ',' every three digits from the right ('{:,}' grouping).
    Input rows are sign+digits (format_i64 output)."""
    n, w = bytes_.shape
    pos = jnp.arange(w, dtype=jnp.int32)[None, :]
    has_sign = (bytes_[:, 0] == 45) | (bytes_[:, 0] == 43)
    sign = has_sign.astype(jnp.int32)
    ndig = lens - sign
    # digit index from the LEFT for each position (sign occupies slot 0)
    didx = pos - sign[:, None]
    inside = (pos < lens[:, None]) & (didx >= 0)
    # commas inserted before this digit = number of complete 3-groups to
    # its right that start after it = (ndig-1-didx) // 3 subtracted from
    # the total; equivalently commas to the LEFT of digit didx:
    total_commas = jnp.maximum(ndig - 1, 0) // 3
    commas_right = jnp.where(inside, (ndig[:, None] - 1 - didx) // 3, 0)
    commas_left = total_commas[:, None] - commas_right
    tgt = jnp.where(inside, pos + commas_left, -1)
    # the sign char stays at position 0 (its didx is -1)
    is_sign_pos = (pos == 0) & has_sign[:, None]
    tgt = jnp.where(is_sign_pos, 0, tgt)
    out_len = (lens + total_commas).astype(jnp.int32)
    wout = w + (max(w, 1) + 2) // 3
    rows = jnp.arange(n)[:, None]
    out = jnp.full((n, wout), ord(","), dtype=jnp.uint8)
    out = _scatter_cols(out, rows, jnp.where(tgt >= 0, tgt, wout),
                        bytes_, wout)
    keep = jnp.arange(wout, dtype=jnp.int32)[None, :] < out_len[:, None]
    return jnp.where(keep, out, 0).astype(jnp.uint8), out_len


def parse_int_base(bytes_, lens, base: int):
    """int(s, base) with a constant base in 2..36. Accepts optional
    surrounding whitespace, one sign, and the matching 0x/0o/0b prefix.
    Returns (value i64, bad bool, overflow bool): `bad` rows raise
    ValueError, `overflow` rows need arbitrary precision (interpreter)."""
    sb, sl = strip(bytes_, lens)
    n, w = sb.shape
    first = sb[:, 0]
    has_sign = ((first == 43) | (first == 45)) & (sl > 0)
    neg = (first == 45) & has_sign
    start = has_sign.astype(jnp.int32)
    prefix = {16: (120, 88), 8: (111, 79), 2: (98, 66)}.get(base)
    if prefix is not None:
        idx0 = jnp.clip(start, 0, w - 1)
        idx1 = jnp.clip(start + 1, 0, w - 1)
        c0 = take_cols(sb, idx0[:, None])[:, 0]
        c1 = take_cols(sb, idx1[:, None])[:, 0]
        has_pref = (c0 == 48) & ((c1 == prefix[0]) | (c1 == prefix[1])) & \
            (sl >= start + 2)
        start = start + jnp.where(has_pref, 2, 0)
    # digit value table: 255 = invalid for this base
    tab = np.full(256, 255, dtype=np.uint8)
    for c in range(256):
        v = None
        if 48 <= c <= 57:
            v = c - 48
        elif 97 <= c <= 122:
            v = c - 87
        elif 65 <= c <= 90:
            v = c - 55
        if v is not None and v < base:
            tab[c] = v
    dig = table_lookup(jnp.asarray(tab), sb.astype(jnp.int32))
    pos = jnp.arange(w, dtype=jnp.int32)[None, :]
    in_digits = (pos >= start[:, None]) & (pos < sl[:, None])
    # CPython accepts '_' separators between digits: exact handling needs
    # positional rules, so underscore rows route to the interpreter
    # (overflow flag) instead of raising
    has_us = jnp.any(in_digits & (sb == 95), axis=1)
    bad = (jnp.any(in_digits & (dig == 255) & (sb != 95), axis=1)
           | (sl <= start))
    # digits such that base**k fits i64 comfortably
    max_digits = 1
    while base ** (max_digits + 1) < 2 ** 62:
        max_digits += 1
    ndig = sl - start
    overflow = (ndig > max_digits) | has_us
    # positional power sum over a bounded window (same technique as
    # parse_i64: no W-step carry chain)
    widx = start[:, None] + jnp.arange(max_digits, dtype=jnp.int32)[None, :]
    wd = take_cols(jnp.where(dig == 255, jnp.uint8(0), dig),
                   jnp.clip(widx, 0, w - 1)).astype(jnp.int64)
    j = jnp.arange(max_digits, dtype=jnp.int32)[None, :]
    exp = jnp.clip(ndig[:, None] - 1 - j, 0, max_digits - 1)
    powers = jnp.asarray(
        np.array([base ** k for k in range(max_digits)], dtype=np.int64))
    term = wd * jnp.take(powers, exp) * (j < ndig[:, None])
    acc = jnp.sum(term, axis=1)
    return jnp.where(neg, -acc, acc), bad, overflow


def int_to_base(vals, base: int, prefix: bool = True):
    """hex()/oct()/bin() rendering: sign + 0x/0o/0b + digits (python
    semantics: hex(-255) == '-0xff'); prefix=False renders the %x/%o
    shape (sign + digits). Returns (bytes, lens)."""
    pref = {16: "0x", 8: "0o", 2: "0b"}[base] if prefix else ""
    n = vals.shape[0]
    neg = vals < 0
    a = jnp.where(neg, -vals, vals).astype(jnp.uint64)
    ndigits = 1
    while base ** ndigits < 2 ** 64:
        ndigits += 1
    digs = []
    cur = a
    for _ in range(ndigits):
        d = (cur % base).astype(jnp.int32)
        digs.append(d)
        cur = cur // base
    # digs[0] = least significant; render most-significant first with
    # leading-zero suppression
    chars = []
    for d in reversed(digs):
        chars.append(jnp.where(d < 10, 48 + d, 87 + d).astype(jnp.uint8))
    mat = jnp.stack(chars, axis=1)                     # [n, ndigits]
    sig = jnp.stack(list(reversed(digs)), axis=1) != 0
    first_sig = jnp.argmax(sig, axis=1).astype(jnp.int32)
    nz = jnp.any(sig, axis=1)
    first_sig = jnp.where(nz, first_sig, ndigits - 1)  # 0 renders '0'
    out_ndig = ndigits - first_sig
    # assemble: sign + prefix + digits (shift digits left)
    head = ("-" + pref, pref)
    hb_neg, hl_neg = broadcast_const(head[0], n)
    hb_pos, hl_pos = broadcast_const(head[1], n, width=hb_neg.shape[1])
    hb = jnp.where(neg[:, None], hb_neg, hb_pos)
    hl = jnp.where(neg, hl_neg, hl_pos)
    db, dl = slice_(mat, jnp.full(n, ndigits, jnp.int32),
                    first_sig, first_sig + out_ndig)
    return concat(hb, hl, db, dl)
