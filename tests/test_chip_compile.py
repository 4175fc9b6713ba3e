"""Compile the main path's device programs for a DESCRIBED TPU v5e, at real
sizes, without a chip (on-chip-measurement guide, section 2): the TPU's own
compiler refuses here what it would refuse there — a Mosaic kernel with an
unaligned slice, a program that does not fit 16 GB — at no chip time.

Nothing runs: these tests say nothing about results or speed. The topology
is described inside a module-scoped fixture (never at import: only one
process may hold libtpu, and every xdist worker imports this file), every
compile happens in the test's own process, and jax's persistent compilation
cache is off around them (an entry for a described chip cannot be read
back). Code that asks ``jax.default_backend()`` sees the CPU here, so the
branches a TPU takes (no fusion barriers, dense NFA, MXU gathers, donation,
packed transfers) are steered with monkeypatch.

All chip compiles live in THIS file: a second file could land on another
xdist worker, where libtpu is already taken.
"""

from __future__ import annotations

import os

import numpy as np
import pytest

HBM_BYTES = 16 * 1024 ** 3          # one v5e chip
DEVICE_BATCH = 1 << 20              # tuplex.tpu.deviceBatchSize


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    from jax.sharding import Mesh

    assert len(topo.devices) == 4
    return Mesh(np.array(topo.devices), ("data",))


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture()
def tpu_branches(monkeypatch):
    """Every ``jax.default_backend()`` read in the framework answers "tpu"
    (fusion_barriers_enabled, _nfa_impl, mxu_gather_override,
    donation_enabled, packing_enabled, the planner's platform)."""
    from tuplex_tpu.runtime import jaxcfg

    monkeypatch.setattr(jaxcfg.jax, "default_backend", lambda: "tpu")


def _fits(compiled, label: str) -> int:
    """Per-device bytes of one execution (arguments + outputs + temporaries
    + code, less aliased), printed (-s shows them) and held under HBM."""
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes + m.generated_code_size_in_bytes
             - m.alias_size_in_bytes)
    print(f"\n[chip-compile] {label}: args={m.argument_size_in_bytes} "
          f"out={m.output_size_in_bytes} temp={m.temp_size_in_bytes} "
          f"code={m.generated_code_size_in_bytes} "
          f"alias={m.alias_size_in_bytes} total={total} "
          f"({total / HBM_BYTES:.1%} of 16 GiB)")
    assert total < HBM_BYTES, (label, total)
    return total


def _scaled(arrays: dict, rows: int, sharding, scalar_sharding=None) -> dict:
    """ShapeDtypeStructs of a staged batch with its row dimension set to
    `rows`, placed by `sharding` (0-d leaves by `scalar_sharding`)."""
    import jax

    out = {}
    for k, v in arrays.items():
        if np.ndim(v) == 0:
            out[k] = jax.ShapeDtypeStruct(
                (), v.dtype, sharding=scalar_sharding or sharding)
        else:
            out[k] = jax.ShapeDtypeStruct((rows,) + tuple(v.shape[1:]),
                                          v.dtype, sharding=sharding)
    return out


def _zillow_stage(tmp_path):
    """(stage, runtime schema, small staged batch) of the fused Zillow
    stage, from Context's own plan over a generated CSV."""
    import tuplex_tpu
    from tuplex_tpu.models import zillow
    from tuplex_tpu.plan.physical import TransformStage, plan_stages
    from tuplex_tpu.runtime import columns as C

    path = str(tmp_path / "zillow.csv")
    zillow.generate_csv(path, 512, seed=11)
    ctx = tuplex_tpu.Context()
    ds = zillow.build_pipeline(ctx.csv(path))
    stages = [s for s in plan_stages(ds._op, ctx.options_store)
              if isinstance(s, TransformStage)]
    assert len(stages) == 1, "zillow must plan as ONE fused stage"
    stage = stages[0]
    assert not stage.force_interpret
    part = stage.source.load_partitions(ctx)[0]
    return stage, part.schema, C.stage_partition(part)


def test_zillow_stage_real_bucket_cpu_branches(tmp_path, one_chip):
    """The fused stage as XLA:CPU traces it (fusion barriers on, bitmask
    NFA) still compiles for the chip at the full device batch."""
    import jax

    stage, schema, batch = _zillow_stage(tmp_path)
    fn = stage.build_device_fn(schema, compaction=True, fused_fold=True)
    c = jax.jit(fn).lower(
        _scaled(batch.arrays, DEVICE_BATCH, one_chip)).compile()
    _fits(c, "zillow stage 1M rows, CPU branches")


def test_zillow_stage_real_bucket_tpu_branches(tmp_path, one_chip,
                                               tpu_branches):
    """The program the chip runs: TPU branches, compaction on, donated
    input, wrapped in the packed single-buffer transfer (exec/local builds
    exactly this for a LocalBackend off the CPU)."""
    import jax

    from tuplex_tpu.runtime import jaxcfg, packing

    assert not jaxcfg.fusion_barriers_enabled()
    assert jaxcfg.donation_enabled() and packing.packing_enabled()
    stage, schema, batch = _zillow_stage(tmp_path)
    assert stage.split_decision is not None \
        and stage.split_decision.k == 1 and not stage.split_decision.over_budget
    fn = stage.build_device_fn(schema, compaction=True, fused_fold=True)
    traced, buf, extras = packing.PackedStageFn(fn, donate=True).traced_for(
        _scaled(batch.arrays, DEVICE_BATCH, None))
    c = jax.jit(traced, donate_argnums=(0,)).lower(
        jax.ShapeDtypeStruct(buf.shape, buf.dtype, sharding=one_chip),
        {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=one_chip)
         for k, v in extras.items()}).compile()
    _fits(c, f"zillow packed stage 1M rows ({buf.shape[0]} B wire), "
             f"TPU branches")


def test_packed_transfer_roundtrip(one_chip, tpu_branches):
    """runtime/packing alone: one u8 buffer unpacked on device into typed
    leaves and packed back (the upload and the download wire)."""
    import jax

    from tuplex_tpu.runtime import packing

    rows = DEVICE_BATCH
    avals = {
        "0#bytes": jax.ShapeDtypeStruct((rows, 64), np.uint8),
        "0#len": jax.ShapeDtypeStruct((rows,), np.int32),
        "1": jax.ShapeDtypeStruct((rows,), np.int64),
        "2": jax.ShapeDtypeStruct((rows,), np.float64),
        "2#valid": jax.ShapeDtypeStruct((rows,), np.bool_),
        "#rowvalid": jax.ShapeDtypeStruct((rows,), np.bool_),
    }
    spec, total = packing._host_spec(avals, check_values=False)

    def roundtrip(buf):
        args = packing._device_unpack(buf, spec)
        obuf, _ospec = packing._device_pack(args)
        return obuf

    c = jax.jit(roundtrip).lower(
        jax.ShapeDtypeStruct((total,), np.uint8, sharding=one_chip)).compile()
    _fits(c, f"packed transfer round trip 1M rows ({total} B)")


def test_dense_nfa_logs_width(one_chip, tpu_branches):
    """The dense (MXU) NFA engine at the logs pipeline's padded line width:
    [1M, 128] bytes through an alternation + class + repeat pattern."""
    import jax

    from tuplex_tpu.ops import nfa

    assert nfa._nfa_impl() == "dense"
    rx = nfa.compile_nfa(r'"(GET|POST|HEAD) \S+ HTTP/1\.\d" \d{3}')
    c = jax.jit(rx.match).lower(
        jax.ShapeDtypeStruct((DEVICE_BATCH, 128), np.uint8,
                             sharding=one_chip),
        jax.ShapeDtypeStruct((DEVICE_BATCH,), np.int32,
                             sharding=one_chip)).compile()
    _fits(c, "dense NFA 1M x 128")


@pytest.mark.parametrize("rows,width,pattern", [
    (65536, 256, r"[a-z]+@[a-z]+\.(com|org)$"),
    (DEVICE_BATCH, 128, r'^\S+ \S+ \S+ \[[\w:/]+\s[+\-]\d{4}\] "\S+'),
])
def test_pallas_nfa_compiles_for_mosaic(one_chip, rows, width, pattern):
    """The Pallas kernel through Mosaic (interpret=False), both anchors:
    the row-major layout was refused here ("cannot statically prove that
    index in dimension 1 is a multiple of 128")."""
    import jax

    from tuplex_tpu.ops import nfa
    from tuplex_tpu.ops.pallas_nfa import match_pallas

    rx = nfa.compile_nfa(pattern)
    c = jax.jit(lambda b, l: match_pallas(rx, b, l, interpret=False)).lower(
        jax.ShapeDtypeStruct((rows, width), np.uint8, sharding=one_chip),
        jax.ShapeDtypeStruct((rows,), np.int32, sharding=one_chip)).compile()
    assert "tpu_custom_call" in c.as_text()
    _fits(c, f"pallas NFA {rows} x {width}")


def _q1_fold_input(tmp_path):
    """(aggregate op, its FoldSpec, a partition of the fold's input, that
    partition staged): the fold's input is the filter stage's OUTPUT (typed
    columns), so that stage runs here on the CPU first."""
    import tuplex_tpu
    from tuplex_tpu.models import tpch
    from tuplex_tpu.plan import aggregates as A
    from tuplex_tpu.plan.physical import AggregateStage, plan_stages
    from tuplex_tpu.runtime import columns as C

    li = str(tmp_path / "lineitem.csv")
    tpch.generate_csv(li, 512)
    ctx = tuplex_tpu.Context()
    stages = plan_stages(tpch.q1(ctx.csv(li))._op, ctx.options_store)
    agg = next(s for s in stages if isinstance(s, AggregateStage))
    spec = A.recognize_fold(agg.op.aggregate_udf)
    assert spec is not None and spec.reducers == ["sum"] * 4
    part = ctx.backend.execute(
        stages[0], stages[0].source.load_partitions(ctx)).partitions[0]
    return agg.op, spec, part, C.stage_partition(part)


def test_q1_fold_and_q19_probe(tmp_path, one_chip, monkeypatch):
    """TPC-H on the chip: Q1's fold expressions + per-key segment sums over
    a full lineitem batch, and Q19's join probe (lower bound of 1M probe
    keys in a sorted 40k-row build side)."""
    import jax
    import jax.numpy as jnp

    from tuplex_tpu.exec import aggexec, joinexec

    _op, spec, part, batch = _q1_fold_input(tmp_path)
    # trace the fold in its TPU branches
    from tuplex_tpu.runtime import jaxcfg

    monkeypatch.setattr(jaxcfg.jax, "default_backend", lambda: "tpu")
    eval_exprs = aggexec._make_eval_exprs(spec, part.schema)

    def fold(arrs, codes):
        datas, ok = eval_exprs(arrs)
        return [jax.ops.segment_sum(jnp.where(ok, d, 0), codes,
                                    num_segments=5) for d in datas]

    c = jax.jit(fold).lower(
        _scaled(batch.arrays, DEVICE_BATCH, one_chip),
        jax.ShapeDtypeStruct((DEVICE_BATCH,), np.int32,
                             sharding=one_chip)).compile()
    _fits(c, "Q1 fold 1M rows")

    probe = joinexec._build_probe_fn(40000, 1)._fn
    c = jax.jit(probe).lower(
        jax.ShapeDtypeStruct((DEVICE_BATCH, 1), np.uint64,
                             sharding=one_chip),
        jax.ShapeDtypeStruct((40000, 1), np.uint64,
                             sharding=one_chip)).compile()
    _fits(c, "Q19 probe 1M keys vs 40k build rows")


# a q1 partition of the benchmark after the filter: 100,000-row chunks of
# which 98.6% pass, in the q8 bucket above (13 x 8192)
Q1_FOLD_ROWS = 106496


@pytest.mark.parametrize("form", ["device-table", "host-codes"])
def test_q1_bykey_fold_one_executable(tmp_path, one_chip, tpu_branches,
                                      form):
    """exec/aggexec's by-key fold as the chip compiles it, at the batch a
    q1 partition has: the key table of 8 slots matched on the device with
    masked float64 (float32-pair) and int64 reductions a slot, and the form
    for more keys than the table's capacity (host-made codes, segment
    reductions inside the same jit)."""
    import time

    import jax

    from tuplex_tpu.exec import aggexec as AE

    op, spec, part, batch = _q1_fold_input(tmp_path)
    kidx = [part.schema.columns.index(c) for c in op.key_columns]
    plan = AE._key_sig_plan(batch.arrays, part.schema, kidx)
    assert plan is not None and all(kind == "str" for kind, *_ in plan)
    arrays = _scaled(batch.arrays, Q1_FOLD_ROWS, one_chip)
    if form == "device-table":
        # two 1-character str keys: 8 bytes + 4 of length each, and the
        # slot-taken byte
        assert AE._sig_width(plan) == 24
        fn = AE._make_bykey_fold(spec, part.schema, kidx)
        key = jax.ShapeDtypeStruct((8, 25), np.uint8, sharding=one_chip)
    else:
        slots = 2 * AE._TABLE_MAX_SLOTS
        fn = AE._make_bykey_fold(spec, part.schema, kidx, slots)
        key = jax.ShapeDtypeStruct((Q1_FOLD_ROWS,), np.int32,
                                   sharding=one_chip)
    t0 = time.perf_counter()
    c = jax.jit(fn).lower(arrays, key).compile()
    print(f"\n[chip-compile] by-key fold ({form}) compiled in "
          f"{time.perf_counter() - t0:.1f} s")
    _fits(c, f"Q1 by-key fold, {form}, {Q1_FOLD_ROWS} rows")
    text = c.as_text()
    assert ("scatter" in text) == (form == "host-codes")


def test_zillow_stage_row_sharded_on_four_chips(tmp_path, mesh4,
                                                tpu_branches):
    """The mesh backend's program: the same fused stage (no compaction, no
    fused fold) with every row array sharded over a 4-device mesh built
    from the described topology. Row-wise work needs no collective, and
    each device holds a quarter of the batch."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    stage, schema, batch = _zillow_stage(tmp_path)
    fn = stage.build_device_fn(schema, compaction=False, fused_fold=False)
    rows = NamedSharding(mesh4, P("data"))
    c = jax.jit(fn).lower(_scaled(batch.arrays, DEVICE_BATCH, rows,
                                  NamedSharding(mesh4, P()))).compile()
    per_dev = _fits(c, "zillow stage 1M rows on a 4-chip mesh (per device)")
    assert per_dev < HBM_BYTES // 2
    err = c.output_shardings["#err"]
    assert len(err.device_set) == 4 and not err.is_fully_replicated
    text = c.as_text()
    assert "all-gather" not in text and "all-to-all" not in text


def test_parse_f64_integer_path(one_chip, monkeypatch):
    """ops/strings.parse_f64 as a TPU traces it: a float64 there is a pair
    of float32, so the decimal->binary conversion runs in 64-bit integer
    arithmetic (u64 multiplies, 32-bit clz, a 56-step long division) that
    the TPU's x64 legalizer has to take — at a lineitem batch's shape."""
    import jax

    from tuplex_tpu.ops import strings as S

    monkeypatch.setattr(S, "f64_is_f32_pair", lambda: True)
    c = jax.jit(S.parse_f64).lower(
        jax.ShapeDtypeStruct((DEVICE_BATCH, 16), np.uint8,
                             sharding=one_chip),
        jax.ShapeDtypeStruct((DEVICE_BATCH,), np.int32,
                             sharding=one_chip)).compile()
    _fits(c, "parse_f64 (integer path) 1M x 16")


_PIPELINE_P = """
import json, sys
sys.path.insert(0, {repo!r})
import tuplex_tpu
from tuplex_tpu.exec import compilequeue as CQ
from tuplex_tpu.plan.physical import TransformStage, plan_stages
c = tuplex_tpu.Context()
which = sys.argv[1]
data = list(range(512))
if which == "P":
    pipes = [c.parallelize(data).map(lambda x: x + 1).map(lambda x: x * 2)
             .map(lambda x: x - 3).map(lambda x: x * x)]
else:
    pipes = [c.parallelize(data).map(lambda x: x + 7),
             c.parallelize(data).map(lambda x: x * 5).map(lambda x: x - 1),
             c.parallelize(data).map(lambda x: x + 2).map(lambda x: x * 3)
             .map(lambda x: x - 4).map(lambda x: x + 5).map(lambda x: x * 6)
             .map(lambda x: x - 7)]
keys, out = [], []
for ds in pipes:
    keys.append([(len(s.ops), s.key()) for s in
                 plan_stages(ds._op, c.options_store)
                 if isinstance(s, TransformStage)])
    out.append(ds.collect()[:3])
c.close()
print(json.dumps({{"keys": keys, "out": out,
                  "stage_compiles": CQ.STATS["stage_compiles"]}}))
"""


def test_plan_and_stored_executables_survive_other_pipelines(tmp_path):
    """Process A runs pipeline P (four operators) in a fresh state
    directory, process B three pipelines of one, two and six operators,
    process C runs P again: the same stage keys as in A, and no compile —
    what B compiled reaches no later plan. The children hold XLA:CPU (a
    child could not load libtpu beside this file's tests)."""
    import json
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = tmp_path / "pipelines.py"
    script.write_text(_PIPELINE_P.format(repo=repo))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               TUPLEX_COMPILE_ISOLATION="thread",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "xla"),
               TUPLEX_AOT_CACHE=str(tmp_path / "aot"))

    def run(which):
        r = subprocess.run([sys.executable, str(script), which],
                           capture_output=True, text=True, env=env,
                           cwd=str(tmp_path), timeout=600)
        assert r.returncode == 0, r.stderr[-2000:]
        return json.loads(r.stdout.splitlines()[-1])

    a, b, c = run("P"), run("others"), run("P")
    assert [[n for n, _ in keys] for keys in a["keys"]] == [[4]]
    assert [[n for n, _ in keys] for keys in b["keys"]] == [[1], [2], [6]]
    assert a["stage_compiles"] == 1 and b["stage_compiles"] == 3
    assert c["keys"] == a["keys"] and c["out"] == a["out"] == [[1, 1, 9]]
    assert c["stage_compiles"] == 0
