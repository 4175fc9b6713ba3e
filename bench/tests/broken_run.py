"""Drives one whole rehearsal of `bench/run.py` in a process of its own with
the timed path broken underneath, for `test_run.py`:

    python broken_run.py <fault> <run.py arguments...>

Faults: `drop_half` (half of the answer's rows left out), `alter_one` (one
value changed where it is produced), `no_answer`, `demoted` (every stage
record names the host-CPU tier)."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "bench"))


def drop_half(out):
    return out[: len(out) // 2]


def alter_one(out):
    """One value of the answer changed: the last field of the middle row,
    or the scalar itself where the answer is one number."""
    out = list(out)
    mid = out[len(out) // 2]
    if isinstance(mid, (int, float)):
        out[len(out) // 2] = mid * 1.001 + 1
        return out
    row = list(mid)
    row[-1] = row[-1] + 1 if isinstance(row[-1], (int, float)) else "x"
    out[len(out) // 2] = tuple(row)
    return out


def no_answer(out):
    return None


def main() -> int:
    import run
    from harness import jobs

    fault = sys.argv[1]
    if fault == "demoted":
        real_check = jobs.check_stage_records

        def demoted(recs, failure_log):
            real_check([dict(m, tier="cpu-compiled") if m.get("tier") else m
                        for m in recs], failure_log)

        jobs.check_stage_records = demoted
    else:
        alter = {"drop_half": drop_half, "alter_one": alter_one,
                 "no_answer": no_answer}[fault]
        real_job = jobs.Runner.job

        def broken(self):
            rec = real_job(self)
            rec["out"] = alter(rec["out"])
            return rec

        jobs.Runner.job = broken
    return run.main(sys.argv[2:])


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(rc)        # as run.py: the program's daemon threads
