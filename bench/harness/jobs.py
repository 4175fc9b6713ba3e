"""One job through the program's normal entry, and the reading of the
program's own records after it: a job that exits cleanly with the chip idle
is a failed job. The checks are those of `chip_smoke.check_records`."""

from __future__ import annotations

import time


class JobFault(Exception):
    """The job ran, but not as the cell asks: work left the device."""


def check_plan(planned) -> None:
    for i, st in enumerate(planned or ()):
        name = f"stage {i} ({type(st).__name__})"
        if getattr(st, "route_reason", ""):
            raise JobFault(f"{name} was routed off the device at plan "
                           f"time: {st.route_reason}")
        if getattr(st, "cpu_compile", False):
            raise JobFault(f"{name} is marked cpu_compile (a host-CPU "
                           f"executable)")


def check_stage_records(recs: list, failure_log: list) -> None:
    if failure_log:
        e = failure_log[0]
        raise JobFault(f"failure_log has {len(failure_log)} entries; first: "
                       f"stage {e.get('stage')} {e.get('action')}: "
                       f"{e.get('error')}")
    fast = 0.0
    for i, m in enumerate(recs):
        tier = m.get("tier")
        if tier is not None and tier != "compiled":
            raise JobFault(f"stage {i} ran on the '{tier}' tier, not the "
                           f"device-compiled one")
        if m.get("tier_restarts"):
            raise JobFault(f"stage {i} restarted {m['tier_restarts']}x "
                           f"down the tier ladder")
        fast += float(m.get("fast_path_s") or 0.0)
    if fast <= 0.0:
        raise JobFault("no stage spent time on the compiled fast path")


def check_executables(platform: str) -> None:
    """Every stage executable in the process was built for `platform`;
    host-pinned ones (salt "/cpupin": the small-batch host resolve policy)
    are on the host CPU by design."""
    from tuplex_tpu.exec import compilequeue as CQ

    for fp, ex in CQ.executable_devices().items():
        if "/cpupin" not in ex["salt"] and \
                any(p != platform for p, _ in ex["devices"]):
            raise JobFault(f"executable {fp[:12]} was built for "
                           f"{ex['devices']}, not for {platform}")
    if CQ.STATS["subprocess_compiles"]:
        raise JobFault(f"{CQ.STATS['subprocess_compiles']} compile(s) "
                       f"forked from the process that holds the chip")


class Runner:
    """Holds the `Context` of one run and drives whole jobs through it."""

    def __init__(self, cell, paths: dict, platform: str):
        import tuplex_tpu

        self.cell = cell
        self.paths = paths
        self.platform = platform
        self.pipe = cell.pipeline()
        self.ctx = tuplex_tpu.Context(dict(cell.context_options))

    def build(self):
        return self.pipe.build(self.ctx, self.paths)

    def job(self) -> dict:
        """One `collect()`. Returns the output, the wall seconds, the stage
        records it left and the fault, if any."""
        ctx = self.ctx
        n0 = len(ctx.metrics.stages)
        fl0 = len(ctx.backend.failure_log)
        rec: dict = {"out": None, "fault": None}
        t0 = time.perf_counter()
        try:
            rec["out"] = self.build().collect()
        except Exception as e:       # a job that raises is a failed job
            rec["fault"] = f"{type(e).__name__}: {e}"
        rec["t0"] = t0
        rec["seconds"] = time.perf_counter() - t0
        rec["stages"] = list(ctx.metrics.stages[n0:])
        if rec["fault"] is None:
            try:
                check_stage_records(rec["stages"],
                                    ctx.backend.failure_log[fl0:])
                check_executables(self.platform)
            except JobFault as e:
                rec["fault"] = str(e)
        return rec

    def plan(self) -> tuple:
        """(planned stages, milliseconds of one `plan_stages` call)."""
        from tuplex_tpu.plan.physical import plan_stages

        ds = self.build()
        t0 = time.perf_counter()
        planned = plan_stages(ds._op, self.ctx.options_store)
        return planned, (time.perf_counter() - t0) * 1e3

    def close(self) -> None:
        self.ctx.close()
