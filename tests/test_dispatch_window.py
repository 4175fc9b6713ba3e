"""A dispatch returns at launch and the wait for the chip is the head of
that partition's collect (`exec/local._dispatch_launch`, `_await_dispatch`):
the window of `tuplex.tpu.dispatchWindow` runs the chip beside the host.

On XLA:CPU, with a stand-in for the stage's device function whose outputs
turn ready on command (`Chip`): the real executable runs, its outputs are
handed on as leaves that say "ready" only when the stand-in lets them, and
every launch, poll and fetch is logged in order."""

import numpy as np
import pytest

import tuplex_tpu
from tuplex_tpu.exec import local as LB
from tuplex_tpu.runtime import devprof as DP
from tuplex_tpu.runtime import tracing as TR

ROWS = 12000


class _Leaf:
    """One output of a launched dispatch: `is_ready` asks the stand-in,
    `__array__` (what `jax.device_get` calls) is the fetch."""

    def __init__(self, chip, k, value):
        self.chip, self.k, self.value = chip, k, value
        self.shape, self.dtype, self.nbytes = \
            value.shape, value.dtype, value.nbytes

    def is_ready(self):
        return self.chip.poll(self.k)

    def __array__(self, dtype=None, copy=None):
        self.chip.fetched(self.k)
        return self.value


class Chip:
    """The stand-in: dispatch `k` turns ready at its `polls_until_ready`-th
    poll (0: ready when the host arrives), or fails at its wait."""

    def __init__(self, polls_until_ready=2, fail_at_wait=()):
        self.polls_until_ready = polls_until_ready
        self.fail_at_wait = set(fail_at_wait)
        self.log: list = []
        self.launched = 0
        self.polls: dict = {}

    def launch(self, real, arrays):
        import jax

        k = self.launched
        self.launched += 1
        self.log.append(("launch", k))
        host = jax.device_get(real(arrays))
        return {name: _Leaf(self, k, np.asarray(v))
                for name, v in host.items()}

    def poll(self, k):
        if k in self.fail_at_wait:
            self.fail_at_wait.discard(k)
            self.log.append(("wait-failed", k))
            raise RuntimeError(f"dispatch {k} failed on the chip")
        n = self.polls[k] = self.polls.get(k, 0) + 1
        if n == 1:
            self.log.append(("wait", k))
        return n > self.polls_until_ready

    def fetched(self, k):
        if ("fetch", k) not in self.log:
            self.log.append(("fetch", k))

    def launches_before_first_wait(self):
        first = next(i for i, e in enumerate(self.log) if e[0] == "wait")
        return sum(1 for e in self.log[:first] if e[0] == "launch")


@pytest.fixture()
def chip(monkeypatch):
    """Every stage function a LocalBackend builds launches through one
    stand-in (replace `chip.polls_until_ready` / `fail_at_wait` to steer)."""
    monkeypatch.setenv("TUPLEX_COMPILE_ISOLATION", "thread")
    c = Chip()
    orig = LB.LocalBackend._jit_stage_fn

    def gated(self, raw_fn, **kw):
        real = orig(self, raw_fn, **kw)
        return lambda arrays: c.launch(real, arrays)

    monkeypatch.setattr(LB.LocalBackend, "_jit_stage_fn", gated)
    return c


@pytest.fixture()
def spans():
    was = TR.enabled()
    TR.enable(True)
    TR.clear()
    yield lambda *names: [e for e in TR.events_since(0)
                          if e["name"] in names and e.get("dur") is not None]
    TR.enable(was)


@pytest.fixture()
def devprof_on():
    DP.clear()
    DP.enable(True)
    yield
    DP.clear()
    DP.enable(True)


def triple(x):
    return x * 3 + 1


def run_job(window, rows=ROWS):
    """(rows out, the stage's record, the backend) of one small job whose
    stage is several dispatches long."""
    ctx = tuplex_tpu.Context({"tuplex.partitionSize": "16KB",
                              "tuplex.tpu.dispatchWindow": window})
    try:
        got = ctx.parallelize(list(range(rows))).map(triple).collect()
        (rec,) = [m for m in ctx.metrics.stages if "dispatches_ready" in m]
        return got, rec, ctx.backend
    finally:
        ctx.close()


WANT = [triple(x) for x in range(ROWS)]


# ---------------------------------------------------------------------------
# (a) a dispatch returns at launch; the window fills before the first collect
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("window, in_flight", [(3, 3), (1, 1), (4, 4)])
def test_the_window_is_launched_before_the_first_collect_begins(
        chip, spans, window, in_flight):
    got, rec, _be = run_job(window)
    assert got == WANT
    n = chip.launched
    assert n >= 6
    # no output was looked at before `window` dispatches were on the chip
    assert chip.launches_before_first_wait() == in_flight
    # and then one collect a launch, in partition order: wait, then fetch
    assert [e for e in chip.log if e[0] != "launch"] == \
        [(what, k) for k in range(n) for what in ("wait", "fetch")]
    waits = spans("dispatch:device-wait")
    assert len(waits) == n
    steady = [w["args"]["in_flight"] for w in waits[:n - in_flight + 1]]
    assert steady == [in_flight] * len(steady)
    # the tail drains: nothing is launched behind the last partitions
    assert [w["args"]["in_flight"] for w in waits[-in_flight:]] == \
        list(range(in_flight, 0, -1))
    # the chip set the pace of every partition (two polls found it busy)
    assert [w["args"]["ready"] for w in waits] == [0] * n
    assert rec["dispatches_waited"] == n and rec["dispatches_ready"] == 0


def test_dispatch_partition_returns_outputs_that_are_not_ready(chip):
    """`_dispatch_partition` itself: back with the launch's stamp while no
    output was polled, blocked on or fetched."""
    from tuplex_tpu.core import typesys as T
    from tuplex_tpu.runtime import columns as C

    be = LB.LocalBackend(tuplex_tpu.Context().options_store)
    part = C.build_partition([(i,) for i in range(64)],
                             T.row_of(["a"], [T.I64]))
    fn = be._jit_stage_fn(lambda arrays: {"0": arrays["0"] + 1},
                          packed=False)
    _part, outs, dispatch_s, launch = be._dispatch_partition(
        part, fn, "returns-at-launch/schema", False, None, packed=False)
    assert chip.log == [("launch", 0)]          # no wait, no fetch
    assert isinstance(launch, LB._Launch) and launch.cold
    assert 0.0 < launch.t and launch.ready_at == 0.0 and dispatch_s >= 0.0
    assert not DP.block_ready({"x": _Leaf(Chip(5), 0, np.zeros(1))}) \
        and chip.polls == {}
    assert all(isinstance(v, _Leaf) for v in outs.values())


# ---------------------------------------------------------------------------
# (b) the span: on the job's thread, inside the collect, before any fetch
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("polls_until_ready, ready", [(2, 0), (0, 1)])
def test_the_wait_span_heads_the_collect_and_closes_before_the_fetch(
        chip, spans, devprof_on, polls_until_ready, ready):
    chip.polls_until_ready = polls_until_ready
    got, rec, _be = run_job(3)
    assert got == WANT
    n = chip.launched
    (job,) = spans("job")
    waits = spans("dispatch:device-wait")
    collects = spans("partition:collect-fast")
    fetches = spans("d2h:leaf-fetch", "d2h:packed-fetch")
    assert len(waits) == len(collects) == len(fetches) == n
    for w, c, f in zip(waits, collects, fetches):
        assert w["tid"] == job["tid"] and w["job"] == job["id"]
        assert w["parent"] == c["id"]           # inside the collect,
        assert f["parent"] == c["id"]
        assert w["ts"] + w["dur"] <= f["ts"]    # closed before the fetch
        # opens also where the outputs were ready when the host came
        assert w["args"]["ready"] == ready and w["args"]["in_flight"] >= 1
    # no wait is left on the dispatch side
    dispatches = {d["id"] for d in spans("partition:dispatch",
                                         "dispatch:launch")}
    assert not any(w["parent"] in dispatches for w in waits)
    assert rec["dispatches_waited"] + rec["dispatches_ready"] == n \
        == rec["device_dispatches"]
    assert rec["dispatches_ready"] == (n if ready else 0)


# ---------------------------------------------------------------------------
# (c) deviant rows: the same rows in the same order at every window
# ---------------------------------------------------------------------------

def _deviant_csv(path):
    """An int column with floats (the general tier) and nulls (TypeError:
    dropped) in it, and a string column with cells too long for the
    columnar path (boxed at ingest: the interpreter)."""
    cells, want = [], []
    for i in range(ROWS):
        a, s = i, f"w{i}"
        if i > 500 and i % 97 == 0:
            a = i + 0.5
        if i > 500 and i % 211 == 0:
            a = None
        if i > 500 and i % 389 == 0:
            s = "w" + "long" * 30
        cells.append(("" if a is None else a, s))
        if a is not None and (a * 2) % 7 != 0:
            want.append((a, s, a * 2))
    with open(path, "w") as fp:
        fp.write("a,s\n")
        fp.writelines(f"{a},{s}\n" for a, s in cells)
    return str(path), want


@pytest.mark.parametrize("window", [1, 3, 4])
def test_deviant_rows_come_back_the_same_at_every_window(
        tmp_path, monkeypatch, window):
    monkeypatch.setenv("TUPLEX_COMPILE_ISOLATION", "thread")
    path, want = _deviant_csv(tmp_path / "d.csv")
    ctx = tuplex_tpu.Context({"tuplex.partitionSize": "32KB",
                              "tuplex.tpu.maxStrBytes": 64,
                              "tuplex.tpu.dispatchWindow": window})
    try:
        got = (ctx.csv(path)
               .withColumn("b", lambda x: x["a"] * 2)
               .filter(lambda x: x["b"] % 7 != 0)).collect()
        recs = [m for m in ctx.metrics.stages if "dispatches_ready" in m]
        rec = {k: sum(m.get(k, 0) for m in recs)    # XLA:CPU may split it
               for k in ("resolve_general_rows", "resolve_interpreter_rows",
                         "exception_rows", "device_dispatches",
                         "dispatches_waited", "dispatches_ready")}
        assert not ctx.backend.failure_log
    finally:
        ctx.close()
    assert [tuple(r) for r in got] == want      # exact, in input order
    assert [type(r[0]) for r in got] == [type(r[0]) for r in want]
    assert rec["resolve_general_rows"] > 50
    assert rec["resolve_interpreter_rows"] > 10
    assert rec["exception_rows"] > 20
    assert rec["device_dispatches"] > 6
    assert rec["dispatches_waited"] + rec["dispatches_ready"] \
        == rec["device_dispatches"]


# ---------------------------------------------------------------------------
# (d) an output that fails at the wait: a failed task, retried, not lost
# ---------------------------------------------------------------------------

def test_an_output_that_fails_at_the_wait_is_retried_not_lost(chip, spans):
    chip.fail_at_wait = {2}
    got, rec, be = run_job(3)
    assert got == WANT                          # nothing lost, in order
    (entry,) = be.failure_log
    assert entry["action"] == "retry" and entry["attempt"] == 1
    assert "dispatch 2 failed on the chip" in entry["error"]
    assert rec["task_failures"] == 1 and rec["tier"] == "compiled"
    # the retry is a launch of its own, collected at once (the two
    # dispatches behind it stay in flight), and nothing was fetched of
    # the dispatch that failed
    n = chip.launched
    i = chip.log.index(("wait-failed", 2))
    assert chip.log[i + 1:i + 4] == [("launch", 5), ("wait", 5),
                                     ("fetch", 5)]
    assert ("fetch", 2) not in chip.log
    # every collected dispatch has its span; the failed wait's closed too
    assert len(spans("dispatch:device-wait")) == n


# ---------------------------------------------------------------------------
# (e) devprof records at the wait, and only records
# ---------------------------------------------------------------------------

def test_a_late_sample_stays_out_of_the_warm_median(devprof_on):
    DP._BY_TAG["late-tag"] = {"fp": DP.StageCost(flops=1e9,
                                                 bytes_accessed=1e6)}
    for s in (0.010, 0.011, 0.012):
        DP.record_dispatch("late-tag", s, rows=10)
    for s in (5.0, 6.0, 7.0, 8.0):      # the host came late: upper bounds
        DP.record_dispatch("late-tag", s, rows=10, late=True)
    acc = DP._DISP[(0, "late-tag")]
    assert acc["warm"] == [0.010, 0.011, 0.012]
    rep = DP.stage_report("late-tag")
    assert rep["device_dispatches"] == 7        # counted in the sums
    assert rep["device_s"] == pytest.approx(26.033)
    assert rep["roofline_frac"] == pytest.approx(
        DP.roofline(1e9, 1e6, 0.011)["roofline_frac"])
    # a stage whose every sample was late falls back as a cold-only one
    DP.record_dispatch("late-tag", 4.0, late=True)
    DP.record_dispatch("late-tag", 2.0, late=True)
    assert DP.stage_report("late-tag")["roofline_frac"] == pytest.approx(
        DP.roofline(1e9, 1e6, 2.0)["roofline_frac"])


def test_waited_samples_are_warm_and_late_ones_flagged(chip, devprof_on,
                                                       monkeypatch):
    seen = []
    real = DP.record_dispatch
    monkeypatch.setattr(
        DP, "record_dispatch",
        lambda tag, s, **kw: (seen.append((s, kw)), real(tag, s, **kw))[1])
    chip.polls_until_ready = 2
    _got, rec, _be = run_job(3)
    assert [kw["late"] for _s, kw in seen] == [False] * chip.launched
    assert seen[0][1]["cold"] and not seen[1][1]["cold"]
    assert all(s >= 0 for s, _kw in seen)
    assert rec["device_s"] == pytest.approx(sum(s for s, _kw in seen))
    assert rec["device_cold_s"] > 0
    seen.clear()
    chip.polls_until_ready = 0
    run_job(3)
    assert [kw["late"] for _s, kw in seen] == [True] * len(seen) and seen


def test_devprof_off_records_nothing_and_keeps_the_schedule(
        chip, devprof_on, monkeypatch):
    got_on, rec_on, _be = run_job(3)
    log_on, chip.log, chip.launched, chip.polls = chip.log, [], 0, {}
    assert rec_on["device_dispatches"] == len(log_on) // 3
    monkeypatch.setenv("TUPLEX_DEVPROF", "0")   # wins over the option
    got_off, rec_off, _be = run_job(3)
    assert not DP.enabled()
    assert got_off == got_on == WANT
    assert chip.log == log_on                   # launch for launch, wait
    assert "device_s" not in rec_off            # for wait, fetch for fetch
    assert "device_dispatches" not in rec_off
    # the pace counters are the stage's own, not devprof's
    assert rec_off["dispatches_waited"] == rec_on["dispatches_waited"] \
        == len(log_on) // 3


# ---------------------------------------------------------------------------
# (f) the answer is boxed at each collect, while the window runs the chip
# ---------------------------------------------------------------------------

def test_each_partition_is_boxed_at_its_collect_before_the_next_wait(
        chip, spans, monkeypatch):
    from tuplex_tpu.runtime import columns as C

    real = C.box_rows
    at_box = []     # (partitions boxed before, launches, polls) at each box

    def logged(part):
        at_box.append((len(at_box), chip.launched, dict(chip.polls)))
        chip.log.append(("box", len(at_box) - 1))
        return real(part)

    monkeypatch.setattr(C, "box_rows", logged)
    got, rec, _be = run_job(3)
    assert got == WANT
    n = chip.launched
    assert len(at_box) == n == rec["partitions_boxed"]
    # partition k: its wait, its fetch, its rows, then the next one's wait
    assert [e for e in chip.log if e[0] != "launch"] == \
        [(what, k) for k in range(n) for what in ("wait", "fetch", "box")]
    for k, launched, polls in at_box:
        # k + 1 and k + 2 are on the chip and were not looked at yet
        assert launched == min(n, k + 3)
        assert all(polls.get(j, 0) == 0 for j in range(k + 1, launched))
    (job,) = spans("job")
    (stage,) = spans("stage:execute")
    boxes = spans("collect:box-partition")
    assert len(boxes) == n
    assert all(b["parent"] == stage["id"] and b["tid"] == job["tid"]
               and b["args"]["in_window"] == 1 for b in boxes)
    assert sum(b["args"]["rows"] for b in boxes) == ROWS
