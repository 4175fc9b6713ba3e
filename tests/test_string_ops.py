"""Device string kernels vs Python string semantics (golden comparison —
the reference tests compiled str methods against CPython the same way,
test/codegen/PythonFunctions.cc)."""

import numpy as np
import pytest

from tuplex_tpu.ops import strings as S
from tuplex_tpu.runtime.jaxcfg import jnp

CORPUS = [
    "hello world",
    "",
    "  padded  ",
    "a",
    "3 bds , 2 ba , 1,560 sqft",
    "Apartment for rent",
    "CONDO, sold: $1,230",
    "aaaa",
    "abcabcabc",
    "-123",
    "+45",
    "  42  ",
    "12.5e3",
    "0",
    "x,y,,z,",
]


def enc(vals):
    return S.from_numpy_strings(vals)


def dec(b, l):
    return S.to_python_strings(b, l)


@pytest.mark.parametrize("needle", [" bd", "a", "", "abc", "zzz", ","])
def test_find_rfind(needle):
    b, l = enc(CORPUS)
    got = np.asarray(S.find_const(b, l, needle))
    want = [s.find(needle) for s in CORPUS]
    assert got.tolist() == want
    got_r = np.asarray(S.find_const(b, l, needle, reverse=True))
    want_r = [s.rfind(needle) for s in CORPUS]
    assert got_r.tolist() == want_r


def test_find_with_start():
    b, l = enc(CORPUS)
    start = np.full(len(CORPUS), 2, dtype=np.int32)
    got = np.asarray(S.find_const(b, l, "a", start=start))
    want = [s.find("a", 2) for s in CORPUS]
    assert got.tolist() == want


@pytest.mark.parametrize("pat", ["a", "he", "", "zzz", "  "])
def test_startswith_endswith_contains(pat):
    b, l = enc(CORPUS)
    assert np.asarray(S.startswith_const(b, l, pat)).tolist() == [
        s.startswith(pat) for s in CORPUS
    ]
    assert np.asarray(S.endswith_const(b, l, pat)).tolist() == [
        s.endswith(pat) for s in CORPUS
    ]
    assert np.asarray(S.contains_const(b, l, pat)).tolist() == [
        pat in s for s in CORPUS
    ]


def test_slice_dynamic():
    b, l = enc(CORPUS)
    n = len(CORPUS)
    start = np.array([1] * n, dtype=np.int32)
    stop = np.array([-2] * n, dtype=np.int32)
    rb, rl = S.slice_(b, l, start, stop)
    assert dec(rb, rl) == [s[1:-2] for s in CORPUS]
    # open ends
    rb, rl = S.slice_(b, l, None, np.full(n, 4, np.int32))
    assert dec(rb, rl) == [s[:4] for s in CORPUS]
    rb, rl = S.slice_(b, l, np.full(n, -3, np.int32), None)
    assert dec(rb, rl) == [s[-3:] for s in CORPUS]


def test_char_at_and_oob():
    b, l = enc(CORPUS)
    n = len(CORPUS)
    ch, cl, oob = S.char_at(b, l, np.zeros(n, np.int32))
    want_ok = [len(s) > 0 for s in CORPUS]
    assert (~np.asarray(oob)).tolist() == want_ok
    got = dec(ch, cl)
    for g, s, ok in zip(got, CORPUS, want_ok):
        if ok:
            assert g == s[0]
    ch, cl, oob = S.char_at(b, l, np.full(n, -1, np.int32))
    for g, s, bad in zip(dec(ch, cl), CORPUS, np.asarray(oob).tolist()):
        assert bad == (len(s) == 0)
        if not bad:
            assert g == s[-1]


def test_case_ops():
    b, l = enc(CORPUS)
    assert dec(*S.lower(b, l)) == [s.lower() for s in CORPUS]
    assert dec(*S.upper(b, l)) == [s.upper() for s in CORPUS]
    assert dec(*S.swapcase(b, l)) == [s.swapcase() for s in CORPUS]


def test_strip_variants():
    b, l = enc(CORPUS)
    assert dec(*S.strip(b, l)) == [s.strip() for s in CORPUS]
    assert dec(*S.strip(b, l, right=False)) == [s.lstrip() for s in CORPUS]
    assert dec(*S.strip(b, l, left=False)) == [s.rstrip() for s in CORPUS]
    assert dec(*S.strip(b, l, chars="x,")) == [s.strip("x,") for s in CORPUS]


@pytest.mark.parametrize(
    "old,new",
    [(",", ""), (",", ";"), ("ab", "X"), ("aa", "b"), ("a", "aa"), ("abc", "")],
)
def test_replace(old, new):
    b, l = enc(CORPUS)
    rb, rl = S.replace_const(b, l, old, new)
    assert dec(rb, rl) == [s.replace(old, new) for s in CORPUS]


def test_concat():
    b, l = enc(CORPUS)
    b2, l2 = enc(list(reversed(CORPUS)))
    rb, rl = S.concat(b, l, b2, l2)
    assert dec(rb, rl) == [a + c for a, c in zip(CORPUS, reversed(CORPUS))]


def test_equals_and_lt():
    a = ["abc", "abd", "ab", "", "abc", "zz"]
    c = ["abc", "abc", "abc", "x", "abd", "za"]
    ab, al = enc(a)
    cb, cl = enc(c)
    assert np.asarray(S.equals(ab, al, cb, cl)).tolist() == [
        x == y for x, y in zip(a, c)
    ]
    assert np.asarray(S.compare_lt(ab, al, cb, cl)).tolist() == [
        x < y for x, y in zip(a, c)
    ]
    assert np.asarray(S.compare_lt(ab, al, cb, cl, or_equal=True)).tolist() == [
        x <= y for x, y in zip(a, c)
    ]


def test_parse_i64():
    vals = ["123", "-5", "+7", "  42  ", "", "12x", "3.5", "007", "99999999999"]
    b, l = enc(vals)
    got, bad, route = S.parse_i64(b, l)
    assert not np.asarray(route).any()
    for s, g, e in zip(vals, np.asarray(got).tolist(), np.asarray(bad).tolist()):
        try:
            want = int(s)
            assert not e, s
            assert g == want, s
        except ValueError:
            assert e, s


def test_parse_f64():
    vals = ["1.5", "-2.25", "1e3", "2.5e-2", "", "x", "3.", ".5", "1.2.3",
            "  7.0 ", "42"]
    b, l = enc(vals)
    got, bad, route = S.parse_f64(b, l)
    assert not np.asarray(route).any()
    for s, g, e in zip(vals, np.asarray(got).tolist(), np.asarray(bad).tolist()):
        try:
            want = float(s)
            assert not e, s
            assert abs(g - want) < 1e-9 * max(1.0, abs(want)), (s, g, want)
        except ValueError:
            assert e, s


def test_format_i64():
    vals = np.array([0, 5, -7, 12345, -99999, 2**40], dtype=np.int64)
    b, l = S.format_i64(vals)
    assert S.to_python_strings(b, l) == [str(int(v)) for v in vals]
    b, l = S.format_i64(vals, width=5, pad_zero=True)
    assert S.to_python_strings(b, l) == ["%05d" % int(v) for v in vals]


def test_parse_i64_19_digit_overflow():
    # ADVICE r1 (low): 19-digit values above i64 max wrapped silently in the
    # Horner loop instead of routing to the interpreter
    vals = ["9223372036854775807",      # i64 max: fine
            "9223372036854775808",      # max+1: must flag bad
            "9999999999999999999",      # 19 nines: must flag bad
            "-9223372036854775807",     # -max: fine
            "1000000000000000000"]      # 19 digits, in range: fine
    b, l = enc(vals)
    got, bad, route = S.parse_i64(b, l)
    route = np.asarray(route).tolist()
    got = np.asarray(got).tolist()
    # over-range values are valid python ints: ROUTE (interpreter), not bad
    assert not np.asarray(bad).any()
    assert route == [False, True, True, False, False]
    assert got[0] == 9223372036854775807
    assert got[3] == -9223372036854775807
    assert got[4] == 10 ** 18


def test_parse_f64_long_mantissa_routes():
    # review finding: '1'+'0'*69 silently parsed to 1e63 via clamped power
    # weights — mantissas beyond the table must ROUTE, never mis-parse
    vals = ["1" + "0" * 69, "9" * 70, "1" + "0" * 28, "1.5e3"]
    b, l = enc(vals)
    got, bad, route = S.parse_f64(b, l)
    got, bad, route = (np.asarray(x).tolist() for x in (got, bad, route))
    assert not any(bad)
    # beyond the 32-char parse window (S._PARSE_WIN): ROUTE, never misparse
    assert route[0] and route[1]
    for i in (2, 3):  # within the window: exact-enough fast path
        assert not route[i]
        want = float(vals[i])
        assert abs(got[i] - want) <= 1e-9 * want


def test_decimal_to_binary53_is_strtod():
    """The integer decimal->binary conversion (the parse_f64 path of a
    device whose float64 is a float32 pair) rounds exactly as CPython's
    float(): M * 2^E == float(Fraction(m) * 10**e), every case."""
    import math
    import random
    from fractions import Fraction

    rnd = random.Random(24)
    ms = [0, 5, 1, 2 ** 53 + 1, 2 ** 63 - 1, 10 ** 18 - 1] + \
        [rnd.getrandbits(63) >> rnd.randint(0, 62) for _ in range(4000)]
    es = [-2, -2, 22, -27, 0, -18] + \
        [rnd.randint(-27, 27) for _ in range(4000)]
    M, E, ok = (np.asarray(x).tolist() for x in S.decimal_to_binary53(
        jnp.asarray(np.array(ms, dtype=np.uint64)),
        jnp.asarray(np.array(es, dtype=np.int32))))
    checked = 0
    for m, e, mm, ee, o in zip(ms, es, M, E, ok):
        if not o:       # left 64 bits: the caller routes the row
            assert e > 0 and m * 5 ** e >= 2 ** 63, (m, e)
            continue
        checked += 1
        assert math.ldexp(mm, ee) == float(Fraction(m) * Fraction(10) ** e), \
            (m, e, mm, ee)
    assert checked > 3000
    # out of range on either axis is flagged, never converted
    _, _, ok = S.decimal_to_binary53(
        jnp.asarray(np.array([7, 2 ** 63], dtype=np.uint64)),
        jnp.asarray(np.array([28, 0], dtype=np.int32)))
    assert not np.asarray(ok).any()


def test_parse_f64_f32_pair_path(monkeypatch):
    """parse_f64 as a TPU traces it (f64_is_f32_pair steered): the value is the
    float32 pair the device holds for CPython's float — hi = RN24(x),
    lo = RN24(x - hi) — so a parsed "0.05" equals the constant 0.05 there
    (TPC-H Q6's boundary); what the integers cannot convert ROUTES."""
    monkeypatch.setattr(S, "f64_is_f32_pair", lambda: True)
    vals = ["0.05", "0.07", "-2.25", "1e3", "2.5e-2", "123456.78", "-0.0",
            "  7.0 ", "9007199254740993", "x", "1.2.3", "",
            "1234567890123456789", "1e-28", "1e40", "inf"]
    b, l = enc(vals)
    got, bad, route = (np.asarray(x).tolist() for x in S.parse_f64(b, l))

    def pair(x):
        hi = np.float32(x)
        return float(np.float64(hi) + np.float64(np.float32(x - np.float64(hi))))

    assert bad == [False] * 9 + [True] * 3 + [False] * 4
    assert route == [False] * 12 + [True] * 4
    for s, g in list(zip(vals, got))[:9]:
        assert g == pair(float(s)), (s, g)
    monkeypatch.setattr(S, "f64_is_f32_pair", lambda: False)
    got, bad, route = (np.asarray(x).tolist() for x in S.parse_f64(b, l))
    assert got[0] == 0.05 and got[8] == float("9007199254740993")
    assert route[12:] == [False, False, False, True]


def test_nfa_regex_golden():
    """Bit-parallel NFA search must agree with python re on EXISTENCE for
    every supported pattern (incl. alternation + unanchored, which the
    anchored engine rejects)."""
    import re

    from tuplex_tpu.ops.nfa import compile_nfa

    strings = ["", "a", "abc", "zabcz", "GET /idx HTTP/1.0", "POST /x",
               "aaab", "xyz", "ab\n", "line\n", "aXb", "2023-04-01",
               "foo123bar", "  spaced  ", "a" * 50 + "b", "no match here"]
    patterns = ["abc", "a+b", "GET|POST", "(GET|POST) /", "a*b", "x?y?z",
                "[0-9]+-[0-9]+", "^abc", "abc$", "^a.*b$", "fo{2}[0-9]{3}",
                "a{2,}b", "(ab)+", r"\d+", r"\s\w+", "line$", "a|b|c",
                "^$", "z$", "\n$", "line\n$", "^\n$", "\n+$", "b$"]
    b, l = enc(strings)
    for pat in patterns:
        rx = compile_nfa(pat)
        got = np.asarray(rx.match(b, l)).tolist()
        want = [re.search(pat, s) is not None for s in strings]
        assert got == want, (pat, [s for s, g, w in
                                   zip(strings, got, want) if g != w])


def test_nfa_regex_e2e_filter(ctx):
    # unanchored alternation in a filter compiles via the NFA path (a
    # module-level `re` import keeps the UDF compilable; __import__ would
    # sink the stage to the interpreter and test nothing)
    import re as _re_mod

    rows = ["GET /a", "POST /b", "PUT /c", "HEAD /d", "GET /e"]
    ds = (ctx.parallelize(rows)
          .filter(lambda s: _re_mod.search("GET|POST", s)))
    assert ds.collect() == ["GET /a", "POST /b", "GET /e"]
    assert ctx.metrics.fastPathWallTime() > 0
    assert not ctx.backend._not_compilable


@pytest.mark.parametrize("impl", ["bitmask", "dense", "pallas"])
def test_nfa_engines_agree_with_re(impl, monkeypatch):
    """All three NFA engines (uint64 bit-parallel, dense-MXU matmul, and
    the Pallas row-blocked kernel in interpret mode) must agree with
    python re on existence for the full supported-pattern matrix."""
    import re

    monkeypatch.setenv("TUPLEX_NFA_IMPL", impl)
    from tuplex_tpu.ops.nfa import compile_nfa

    strings = ["", "a", "abc", "zabcz", "GET /idx HTTP/1.0", "aaab",
               "ab\n", "aXb", "2023-04-01", "foo123bar", "a" * 50 + "b"]
    patterns = ["abc", "a+b", "GET|POST", "a*b", "[0-9]+-[0-9]+",
                "^abc", "abc$", "^a.*b$", r"\d+", "(ab)+", "^$", "b$"]
    b, l = enc(strings)
    for pat in patterns:
        rx = compile_nfa(pat)
        got = np.asarray(rx.match(b, l)).tolist()
        want = [re.search(pat, s) is not None for s in strings]
        assert got == want, (impl, pat,
                             [s for s, g, w in zip(strings, got, want)
                              if g != w])


def test_regex_rigid_deaths_are_authoritative():
    """r4: deaths behind rigid run boundaries (disjoint follower) must NOT
    route as suspects — malformed logs lines stay on device — while
    overlapping-follower patterns keep their fail-safe routing."""
    from tuplex_tpu.ops.regex import CompiledRegex

    rigid = CompiledRegex(r"^(\d+) (\d+) \[(\w+)\]$")
    assert rigid.first_var == len(rigid.steps)   # fully rigid
    vals = ["12 34 [ok]", "broken line", "1 2 x", "", "9 9 [a b]"]
    b, l = enc(vals)
    matched, suspect, gs, ge = rigid.match(b, l)
    assert not np.asarray(suspect).any()
    import re as _re

    want = [bool(_re.search(r"^(\d+) (\d+) \[(\w+)\]$", s))
            for s in vals]
    assert np.asarray(matched).tolist() == want

    # logs-shaped pattern: '"' IN \S makes the quoted part soft (retreat),
    # but rows dying EARLIER (at the [..] section) are still authoritative
    lg = CompiledRegex(r'^(\S+) (\S+) \[(\w+)\] "(\S+)" (\d+)$')
    assert 0 < lg.first_var < len(lg.steps)
    vals2 = ["broken line", "a b nobracket rest", "a b"]
    b2, l2 = enc(vals2)
    m2, s2, _, _ = lg.match(b2, l2)
    assert not np.asarray(m2).any()
    assert not np.asarray(s2).any()     # early rigid deaths: no routing

    # overlapping follower without retreat support: suspect from the run
    soft = CompiledRegex(r"^(\w+)x$")
    assert soft.first_var < len(soft.steps)
    b2, l2 = enc(["aax", "aaa", "x"])
    m2, s2, _, _ = soft.match(b2, l2)
    # 'aaa': \w+ eats all, 'x' fails; backtracking can't help here but the
    # engine must stay fail-safe (route), never claim an authoritative no
    assert np.asarray(s2)[1]


def test_regex_retreat_failures_still_route():
    import re as _re

    from tuplex_tpu.ops.regex import CompiledRegex

    rx = CompiledRegex(r"^(\d+)0$")
    vals = ["100", "90", "99", "0", "10"]
    b, l = enc(vals)
    matched, suspect, gs, ge = rx.match(b, l)
    for i, s in enumerate(vals):
        pym = _re.search(r"^(\d+)0$", s)
        if np.asarray(suspect)[i]:
            continue    # routed: interpreter decides (always correct)
        assert bool(np.asarray(matched)[i]) == bool(pym), s
        if pym:
            g1 = s[np.asarray(gs[1])[i]:np.asarray(ge[1])[i]]
            assert g1 == pym.group(1), (s, g1)


@pytest.mark.parametrize("impl", ["dense", "pallas"])
def test_nfa_engine_pipeline_end_to_end(impl, monkeypatch, tmp_path):
    """The alternative NFA engines must be green at the PIPELINE level, not
    just the unit matrix: the logs-regex model (re.search existence inside a
    compiled filter) end-to-end under TUPLEX_NFA_IMPL=dense/pallas, checked
    against the pure-python reference. The pallas leg runs the row-blocked
    kernel in interpret mode on CPU (same kernel body Mosaic lowers on
    TPU)."""
    monkeypatch.setenv("TUPLEX_NFA_IMPL", impl)
    import tuplex_tpu
    from tuplex_tpu.models import logs

    p = tmp_path / "access.txt"
    logs.generate_log(str(p), 900)   # not a multiple of the 256-row block
    ctx = tuplex_tpu.Context()
    got = logs.build_pipeline(ctx.text(str(p)), "regex").collect()
    want = logs.run_reference_python(str(p), "regex")
    assert got == want
    assert ctx.metrics.fastPathWallTime() > 0, \
        "regex filter fell off the compiled path"


@pytest.mark.parametrize("n", [1, 7, 256, 257])
def test_pallas_nfa_row_block_edges(n, monkeypatch):
    """Row counts straddling the 256-row kernel block: padding rows must
    not leak matches and real rows must all be scanned."""
    import re

    monkeypatch.setenv("TUPLEX_NFA_IMPL", "pallas")
    from tuplex_tpu.ops.nfa import compile_nfa

    strings = [("ab" if i % 3 == 0 else f"x{i}") for i in range(n)]
    b, l = enc(strings)
    rx = compile_nfa("a+b$")
    got = np.asarray(rx.match(b, l)).tolist()
    assert got == [re.search("a+b$", s) is not None for s in strings]
