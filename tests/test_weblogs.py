"""The weblogs deployment (Tuplex, SIGMOD'21, section 6.1, regex mode) on
the CPU at a small size: the pipeline of
`bench/configs/weblogs-regex/weblogs.py` through `Context` -> `ctx.csv`
against that file's plain CPython reference, in one compiled stage; the
anchored `re.sub` with a per-row replacement against CPython's; lines the
pattern cannot match decided on the device; the rows-a-batch estimate of
graphlint's static-peak check for a one-string-column stage; the six
configurations the benchmark had before keep their planned stages."""

import csv
import importlib.util
import os
import random
import re
import sys

import pytest

from test_emitter import run_compiled, run_interp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench")
CONFIG_DIR = os.path.join(BENCH, "configs", "weblogs-regex")
PARAMS = {"clients": 200000, "client_zipf": 1.0, "date": "2019-03-12",
          "zone": "-0400", "ident_user_dash": 0.98, "endpoint_mu": 4.25,
          "endpoint_sigma": 0.75, "long_tail": 0.001, "malformed": 0.01,
          "home_pages": 0.15, "bad_ip_share": 0.05, "no_size": 0.10,
          "query_strings": 0.20}
LIMITS = {"rows_missing_or_extra": 0, "rows_differ": 0,
          "letters_not_upper": 0, "letter_share_off": 1}
ROWS = 2500


def _load(path, name):
    """A file of the benchmark by path, registered in `sys.modules` so that
    the program's reflection finds the UDFs' source."""
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


GEN = _load(os.path.join(CONFIG_DIR, "generate.py"), "weblogs_generate")
WL = _load(os.path.join(CONFIG_DIR, "weblogs.py"), "weblogs_weblogs")


@pytest.fixture(autouse=True)
def _as_on_the_chip(monkeypatch):
    """On the chip the compile queue never forks; hold XLA:CPU to that."""
    monkeypatch.setenv("TUPLEX_COMPILE_ISOLATION", "thread")


@pytest.fixture()
def spans():
    from tuplex_tpu.runtime import tracing

    was = tracing.enabled()
    tracing.enable(True)
    tracing.clear()
    yield lambda name: [e for e in tracing.events() if e["name"] == name]
    tracing.enable(was)


def tables(seed, rows=ROWS, bad=300, **over):
    params = dict(PARAMS, _rows={"logs": rows, "bad_ips": bad}, **over)
    return {t: GEN.gen_chunk(t, random.Random(f"{seed}:{t}:0"), n, 0, params)
            for t, n in (("logs", rows), ("bad_ips", bad))}


def write(tmp_path, tabs):
    paths = {}
    for t, rows in tabs.items():
        paths[t] = str(tmp_path / f"{t}.csv")
        with open(paths[t], "w", newline="") as fp:
            w = csv.writer(fp)
            w.writerow(GEN.COLUMNS[t])
            w.writerows(rows)
    return paths


def reference(tabs, control=False):
    side = {"bad_ips": {"columns": GEN.COLUMNS["bad_ips"],
                        "rows": tabs["bad_ips"]}}
    return WL.reference_merge(
        [WL.reference_partial(GEN.COLUMNS["logs"], tabs["logs"], side,
                              control)], control)


def failing(compared):
    return {name for name, value, limit in compared if not value <= limit}


# ---- the pipeline through Context against the plain reference ----

@pytest.mark.parametrize("seed", [7, 4000000039])
def test_the_pipeline_equals_the_reference_in_one_compiled_stage(
        tmp_path, spans, seed):
    import tuplex_tpu
    from tuplex_tpu.plan.physical import TransformStage, plan_stages

    tabs = tables(seed)
    want = reference(tabs)
    assert 80 < len(want) < 200 and sum(map(WL.anonymized, want)) > 5
    paths = write(tmp_path, tabs)
    ctx = tuplex_tpu.Context({"tuplex.tpu.compileDeadlineS": 900})
    try:
        planned = plan_stages(WL.build(ctx, paths)._op, ctx.options_store)
        got = WL.build(ctx, paths).collect()
        stages = list(ctx.metrics.stages)
    finally:
        ctx.close()
    assert not failing(WL.compare(got, want, LIMITS))
    # the parse, the anonymization and the projection: one stage, neither
    # split nor degraded by the static-peak check
    first = planned[0]
    assert isinstance(first, TransformStage) and not first.force_interpret
    assert [type(o).__name__ for o in first.ops] == [
        "DecodeOperator", "MapOperator", "WithColumnOperator",
        "SelectColumnsOperator"]
    assert first.static_peak["remedy"] == "none"
    assert all(not getattr(s, "force_interpret", False) for s in planned)
    recs = [m for m in stages if m.get("tier") is not None]
    assert recs and all(m["tier"] == "compiled" for m in recs)
    (parse,) = [m for m in stages if "regex_fallback_rows" in m]
    assert parse["fast_path_s"] > 0 and parse["rows_seen"] == ROWS
    # only the long-tail lines, whose path passes the group window
    tail = sum(1 for (line,) in tabs["logs"]
               if len(WL.ParseWithRegex(line)["endpoint"]) > 256)
    assert parse["regex_fallback_rows"] == tail
    assert parse["resolve_interpreter_rows"] == tail
    # the compile span names the two sites and what runs them
    traced = [e["args"] for e in spans("compile:trace")
              if (e.get("args") or {}).get("regex_sites")]
    assert traced and all(a["regex_sites"] == 2 for a in traced)
    assert {a["regex_engine"] for a in traced} == {
        "direct+nfa:bitmask,direct"}


def test_the_chain_without_long_lines_never_leaves_the_device(tmp_path):
    import tuplex_tpu

    tabs = tables(11, long_tail=0.0)
    paths = write(tmp_path, tabs)
    ctx = tuplex_tpu.Context({"tuplex.tpu.compileDeadlineS": 900})
    try:
        got = WL.build(ctx, paths).collect()
        (parse,) = [m for m in ctx.metrics.stages
                    if "regex_fallback_rows" in m]
    finally:
        ctx.close()
    assert not failing(WL.compare(got, reference(tabs), LIMITS))
    # 25 malformed lines: decided on the device as no match
    assert parse["regex_fallback_rows"] == 0
    assert parse["unexpected_code_rows"] == 0
    assert parse["resolve_interpreter_rows"] == 0


# ---- the capture groups' windows, read off the sample ----

def test_the_group_windows_come_from_the_sample(tmp_path):
    """The parse's groups slice to the windows the operator's sample gives
    them (compiler/regexprof.py), not to one width for every group: at a
    1,152-byte line the stage's string outputs are the address 16 B, the
    date 32, the method and the protocol 8, and the anonymized endpoint
    its 256-byte window plus the replacement's 12."""
    import tuplex_tpu
    from tuplex_tpu.plan.physical import abstract_batch_arrays, plan_stages
    from tuplex_tpu.runtime.jaxcfg import jax
    import numpy as np

    paths = write(tmp_path, tables(7))
    ctx = tuplex_tpu.Context({"tuplex.tpu.compileDeadlineS": 900})
    try:
        first = plan_stages(WL.build(ctx, paths)._op, ctx.options_store)[0]
    finally:
        ctx.close()
    parse = first.ops[1]
    assert list(parse.regex_widths().values()) == [
        (16, 8, 8, 32, 8, 256, 8, 8, 8)]
    arrays = {k: jax.ShapeDtypeStruct((8, 1152), np.uint8)
              if k.endswith("#bytes") else v
              for k, v in abstract_batch_arrays(first.input_schema).items()}
    out = jax.eval_shape(first.build_device_fn(first.input_schema), arrays)
    assert {k: v.shape[1] for k, v in out.items()
            if k.endswith("#bytes")} == {
        "0#bytes": 16, "1#bytes": 32, "2#bytes": 8, "3#bytes": 268,
        "4#bytes": 8}


def _helper_search(s):
    m = re.search(r"^(\w+) (\w+)", s)
    return m.group(2) if m else ""


def _calls_the_helper(s):
    return _helper_search(s)


def _with_flags(s):
    m = re.search(r"^(\w+)", s, re.I)
    return m.group(1) if m else ""


def _match(s):
    m = re.match(r"(\w+)-", s)
    return m.group(1) if m else ""


@pytest.mark.parametrize("udf,rows,want", [
    # a hundredth of the rows long: the window leaves them to route
    (_calls_the_helper,
     [f"a{i % 7} {'w' * (5 + i % 20)}" for i in range(990)]
     + [f"a {'L' * 900}"] * 10,
     {r"^(\w+) (\w+)": (8, 32)}),
    # more than a hundredth of the rows long: the window takes them
    (_calls_the_helper,
     [f"a {'w' * 9}"] * 980 + [f"a {'L' * 900}"] * 20,
     {r"^(\w+) (\w+)": (8, 1024)}),
    # a pattern matched on fewer rows than branchprof.MIN_TRIALS
    (_calls_the_helper, [f"a {'w' * 9}"] * 99, {}),
    # flags: the emitter compiles no such site, so nothing is recorded
    (_with_flags, ["abc"] * 200, {}),
    # re.match: keyed by the anchored pattern the emitter compiles
    (_match, ["x" * 40 + "-"] * 200, {r"^(\w+)-": (64,)}),
], ids=["one_long_line_in_a_hundred", "two_in_a_hundred",
        "too_few_matches", "flags", "match_anchored"])
def test_group_windows_of_a_sample(udf, rows, want):
    from tuplex_tpu.compiler.regexprof import profile_group_widths
    from tuplex_tpu.utils.reflection import get_udf_source

    got = profile_group_widths(get_udf_source(udf), rows,
                               lambda f, r: f(r))
    assert got == want


# ---- re.sub with an anchored pattern and a per-row replacement ----

def _anon(x):
    return re.sub("^/~[^/]+", "/~" + x[1], x[0])


def _const_anchored(x):
    return re.sub("^a+", "X", x[0])


def _template(x):
    return re.sub("^/~[^/]+", x[1], x[0])


def _backreference(x):
    return re.sub("^/~([^/]+)", "/~\\1x", x[0])


WIDE = "/~" + "n" * 40 + "/" + "p" * 300


ROUTED = ("REGEXFALLBACK", "NORMALCASEVIOLATION")


@pytest.mark.parametrize("udf,rows,routed", [
    (_anon, [("/~jsmith/a/b", "ABCDEFGHIJ"), ("/~bob", "QWERTYUIOP")], 0),
    (_anon, [("/x/y", "ABCDEFGHIJ"), ("/~/q", "ABCDEFGHIJ")], 0),
    (_anon, [("/a/~bob/~c", "ABCDEFGHIJ"), ("a/~b", "ABCDEFGHIJ")], 0),
    (_anon, [("", "ABCDEFGHIJ"), ("/~a", "")], 0),
    (_anon, [("/~a", "Z" * 40), (WIDE, "SHORT"), ("/~b", "ABC")], 0),
    (_const_anchored, [("aab", "-"), ("baa", "-"), ("", "-")], 0),
    (_anon, [("/~jösé/x", "ABCDEFGHIJ"), ("/~ok/x", "ABCDEFGHIJ")], 1),
    (_template, [("/~jsmith/x", "/~\\g<0>"), ("/~ann/y", "/~B")], 1),
    (_backreference, [("/~a/b", "-")], None),
], ids=["match_at_0", "no_match", "match_only_later", "empty",
        "wider_than_the_width_bucket", "constant_replacement", "non_ascii",
        "backslash_in_the_replacement", "backreference"])
def test_anchored_sub_with_a_per_row_replacement(udf, rows, routed):
    """Against CPython's re.sub row by row. A row the compiled path
    routes (a non-ASCII subject, a replacement holding a backslash) is
    answered by the interpreter; a constant backreference replacement
    does not compile at all."""
    from tuplex_tpu.core.errors import NotCompilable

    if routed is None:
        with pytest.raises(NotCompilable):
            run_compiled(udf, rows)
        return
    want = run_interp(udf, rows)
    got = run_compiled(udf, rows)
    assert sum(g in ROUTED for g in got) == routed, got
    assert [g for g in got if g not in ROUTED] == \
        [w for g, w in zip(got, want) if g not in ROUTED]


# ---- lines the pattern cannot match, decided on the device ----

MALFORMED = ['1.2.3.4 - - [12/Mar/2019:00:01:57 -0400] "-" 400 152',
             '1.2.3.5 - - [12/Mar/2019:00:01:58 -0400] "\\x16\\x03\\x01" '
             '400 -',
             '1.2.3.6 - - [12/Mar/2019:00:01:59 -0400] "quit" 400 13',
             '1.2.3.7 - - [12/Mar/2019:00:02:00 -0400] "GET /a HTTP/1.1" '
             '200 512',
             'garbage line without any structure']


def test_malformed_lines_are_decided_on_the_device():
    def parse(x):
        return WL.ParseWithRegex(x)

    rows = MALFORMED * 3
    got = run_compiled(parse, rows)
    # the dict rows come back as tuples of their values
    assert got == [tuple(WL.ParseWithRegex(r).values()) for r in rows]
    assert sum(1 for g in got if g[0] == "") == 12


def test_a_suspect_row_the_block_cannot_hold_stays_suspect():
    import numpy as np

    from tuplex_tpu.ops.regex import compile_regex
    from tuplex_tpu.runtime.jaxcfg import jnp

    rx = compile_regex(r'^(\S+) "(\S+) (\S+)\s*(\S*)\s*" (\d{3})')
    lines = ['a "-" 400'] * 3 + ['a "x ' + "y" * 300 + '" 400',
                                'a "GET /x HTTP/1.1" 200', 'a "GET /y" 200']
    w = max(len(s) for s in lines)
    mat = np.zeros((len(lines), w), np.uint8)
    for i, s in enumerate(lines):
        mat[i, :len(s)] = np.frombuffer(s.encode(), np.uint8)
    lens = jnp.asarray([len(s) for s in lines], dtype=jnp.int32)
    alive, suspect, _, _ = rx.match(jnp.asarray(mat), lens)
    decided = rx.match_decided(jnp.asarray(mat), lens)
    assert np.asarray(suspect)[:3].all()
    # the three no-matches are decided; the matches python finds only by
    # backtracking stay suspect, and so does a row past the block's width
    assert np.asarray(decided[1]).tolist() == [False] * 3 + [True, False,
                                                             True]
    assert [re.search(rx.pattern, s) is not None for s in lines] == \
        [False] * 3 + [True, True, True]


# ---- graphlint's rows-a-batch estimate for a one-string-column stage ----

def test_the_static_peak_counts_a_string_columns_bytes(tmp_path):
    import tuplex_tpu
    from tuplex_tpu.plan.physical import plan_stages

    tabs = tables(5)
    paths = write(tmp_path, tabs)
    ctx = tuplex_tpu.Context({"tuplex.tpu.compileDeadlineS": 900})
    try:
        first = plan_stages(WL.build(ctx, paths)._op, ctx.options_store)[0]
        rep = first.graph_report
        widest = max(len(r[0]) for r in tabs["logs"][:1000])
        est = first.static_peak["est_rows"]
        # 32 MB of lines some hundreds of bytes wide, not of 8-byte probes
        assert est == (32 << 20) // (rep.input_row_bytes
                                     + _bucket(widest) - 8)
        assert est < (32 << 20) // 300
        assert (32 << 20) // rep.input_row_bytes > 1_000_000
        assert first.static_peak["peak_bytes"] < 1 << 30
        # a budget the stage does not fit still degrades it, as before
        ctx2 = tuplex_tpu.Context({"tuplex.executorMemory": "1MB"})
        small = plan_stages(WL.build(ctx2, paths)._op, ctx2.options_store)
        assert small[0].static_peak["remedy"] != "none" or \
            small[0].force_interpret
        ctx2.close()
    finally:
        ctx.close()


def _bucket(n):
    from tuplex_tpu.runtime.columns import bucket_size

    return bucket_size(n, minimum=8)


# ---- the configurations the benchmark had keep their plans ----

# (stage kind, operators, degraded) of each cell's plan at 2,000 fact rows
# on XLA:CPU, as the tree before the weblogs deployment planned them
PLANS = {
    "tpch-sf033.q1": [("TransformStage", 2, False),
                      ("AggregateStage", 0, False)],
    "tpch-sf033.q19": [("TransformStage", 4, False), ("JoinStage", 0, False),
                       ("TransformStage", 1, False),
                       ("AggregateStage", 0, False)],
    "zillow-z1-host4.dirty6": [("TransformStage", 13, False)],
    "zillow-z1.dirty6": [("TransformStage", 13, False)],
    "zillow-z1.postal18": [("TransformStage", 13, False)],
    "flights-bts.cancelled2": [
        ("TransformStage", 22, False), ("TransformStage", 22, False),
        ("JoinStage", 0, False), ("TransformStage", 1, False),
        ("JoinStage", 0, False), ("JoinStage", 0, False),
        ("TransformStage", 25, False)],
}


@pytest.mark.parametrize("cell", sorted(PLANS))
def test_the_other_configurations_plan_the_same_stages(tmp_path, cell):
    sys.path.insert(0, BENCH)
    from harness import datagen, spec

    import tuplex_tpu
    from tuplex_tpu.plan.physical import plan_stages

    class Inline:
        def submit(self, fn, *args):
            from concurrent.futures import Future

            f = Future()
            f.set_result(fn(*args))
            return f

    c = spec.Cell(cell, root=REPO)
    c.scale_rows(2000)
    inp = datagen.generate(Inline(), c, 7, str(tmp_path / "work"))
    ctx = tuplex_tpu.Context(dict(c.context_options))
    try:
        planned = plan_stages(c.pipeline().build(ctx, inp.paths)._op,
                              ctx.options_store)
    finally:
        ctx.close()
    assert [(type(s).__name__, len(getattr(s, "ops", None) or ()),
             bool(getattr(s, "force_interpret", False)))
            for s in planned] == PLANS[cell]


# ---- the model's chain, over the text source upstream reads ----

def test_the_models_chain_reads_text_and_equals_its_reference(tmp_path):
    import inspect

    import tuplex_tpu
    from tuplex_tpu.models import logs

    # the benchmark's UDFs are the model's, word for word
    for name in ("ParseWithRegex", "randomize_udf"):
        assert inspect.getsource(getattr(WL, name)) == \
            inspect.getsource(getattr(logs, name)), name
    log = str(tmp_path / "access.log")
    bad = str(tmp_path / "bad_ips.csv")
    logs.generate_log(log, 600, seed=39)
    logs.generate_bad_ips(bad, log, n=40, seed=39)
    want = logs.run_reference_malicious(log, bad)
    assert len(want) > 10 and any(map(WL.anonymized, want))
    ctx = tuplex_tpu.Context({"tuplex.tpu.compileDeadlineS": 900})
    try:
        got = logs.build_malicious_pipeline(ctx, log, bad).collect()
        tiers = {m.get("tier") for m in ctx.metrics.stages} - {None}
    finally:
        ctx.close()
    assert tiers == {"compiled"}
    assert not failing(WL.compare(got, want, LIMITS))


# ---- the benchmark's reader of the new counter ----

def test_regex_fallback_share_reads_the_stages_that_carry_the_counter():
    reader = _load(os.path.join(BENCH, "layer_metrics",
                                "regex_fallback_share.py"),
                   "weblogs_regex_fallback_share")
    sys.path.insert(0, BENCH)

    def run(stages):
        return {"window": {"stages": stages}}

    stages = [{"rows_seen": 1000, "regex_fallback_rows": 3},
              {"rows_seen": 1000, "regex_fallback_rows": 1},
              {"rows_seen": 50}]              # a join's stage: no sites
    assert reader.read(run(stages)) == pytest.approx(0.2)
    assert reader.read(run([{"rows_seen": 10, "regex_fallback_rows": 0}])) \
        == 0.0
    # a program without the counter, or a pipeline without a regex site
    assert reader.read(run([{"rows_seen": 50}])) is None
    assert reader.read(run([])) is None
