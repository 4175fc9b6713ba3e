"""Serverless worker entry point (reference: awslambda/src/lambda_main.cc —
the Lambda-side handler that parses the InvocationRequest, JIT-compiles the
shipped stage, processes its input split, and writes output parts).

Two modes:

* ``python -m tuplex_tpu.exec.worker <request.pkl>`` — one task, then exit
  (the cold-start Lambda invocation).
* ``python -m tuplex_tpu.exec.worker --serve`` — WARM worker: read request
  paths line-by-line from stdin and process each in this long-lived process
  (reference: Lambda container reuse across invocations,
  AWSLambdaBackend.cc:254-430 relies on warm containers the same way).
  Completion is signalled by the atomic ``response.pkl`` write, never by
  process exit; a task exception produces ``{"ok": False}`` instead of
  killing the worker. The interpreter+jax import (~6 s) and every traced
  stage executable (keyed by content hash, TransformStage.key) amortize
  across tasks — measured 15 s/task cold vs sub-second warm on zillow.

The request carries the stage spec (UDF sources + schemas), this task's
input (either a file-split subset or a staged-partition directory), the
output directory, and the full option set. The worker rebuilds the stage,
executes it through the ordinary LocalBackend (fast path + general tier +
interpreter resolve — the full dual-mode ladder, unlike the reference
Lambda which defers the slow path to the driver), and writes native-format
output parts plus a pickled response (metrics, exceptions).

Platform: ``TUPLEX_WORKER_PLATFORM`` (set by the driver from
``tuplex.aws.workerPlatform``) picks the jax platform POST-import — on
machines where a TPU plugin force-registers itself, only a late
``jax.config.update`` wins over the environment.
"""

from __future__ import annotations

import os
import pickle
import sys


def _set_platform() -> None:
    plat = os.environ.get("TUPLEX_WORKER_PLATFORM", "")
    if plat:
        import jax

        jax.config.update("jax_platforms", plat)


def run_task(req_path: str, backends=None) -> dict:
    """Process one request pickle; returns the response dict (also written
    atomically to response.pkl next to the request). `backends` is a
    per-process cache {options-fingerprint: LocalBackend} — an
    LRU-bounded mapping (utils/lru.LruDict in serve()) so warm workers
    reuse traced stage executables across tasks AND across interleaved
    tenants with different option sets."""
    import json
    import time

    with open(req_path, "rb") as fp:
        req = pickle.load(fp)

    task_dir = os.path.dirname(os.path.abspath(req_path))
    t_start = time.time()

    def emit(kind: str, **fields) -> None:
        """Append a live task event for the driver's poll loop to stream
        into the history dashboard (reference: Lambda workers posting task
        status back, HistoryServerConnector.cc:102-198). Best-effort: an
        unwritable control dir must never fail the task."""
        try:
            with open(os.path.join(task_dir, "events.jsonl"), "a") as efp:
                efp.write(json.dumps(
                    {"event": kind, "pid": os.getpid(), **fields}) + "\n")
        except OSError:
            pass

    emit("started", task=req.get("task"),
         input=(req.get("files") or req.get("indir") or "memory"))

    from ..core.options import ContextOptions
    from ..exec.local import LocalBackend
    from ..io.tuplexfmt import (TuplexFileSourceOperator,
                                write_partitions_tuplex)
    from .serverless import rebuild_stage

    opts_dict = dict(req["options"])
    # workers are leaves: never recurse into another fan-out, never serve UI
    opts_dict["tuplex.backend"] = "local"
    opts_dict["tuplex.webui.enable"] = "false"
    fing = tuple(sorted(opts_dict.items()))
    backend = None if backends is None else backends.get(fing)
    if backend is None:
        options = ContextOptions(opts_dict)
        backend = LocalBackend(options)
        if backends is not None:
            # bounded LRU, NOT one-live-set: interleaved tenants with
            # different option fingerprints used to rebuild backends (and
            # lose every traced stage executable) on each alternation
            backends[fing] = backend
    options = backend.options
    fl_snap = len(backend.failure_log)

    stage = rebuild_stage(req["stage"], options, files=req.get("files"))

    class _Ctx:   # minimal context for source loading (duck-typed)
        options_store = options

        def __init__(self):
            self.backend = backend

    ctx = _Ctx()
    if req.get("indir"):
        src = TuplexFileSourceOperator(options, req["indir"])
        partitions = src.load_partitions(ctx)
    else:
        from ..api.dataset import _source_partitions

        # a task's whole share, as `load_partitions` hands it over
        partitions = list(_source_partitions(ctx, stage, lazy=False))

    result = backend.execute(stage, partitions)

    sink = req.get("sink")
    if sink is not None:
        # sink pushdown: this task's rows become its own part file written
        # straight from columnar buffers (reference: Lambda writing S3
        # output.part-N); no partitions travel back
        from .serverless import write_sink_part

        write_sink_part(sink, req["task"], result.partitions,
                        backend=backend)
    else:
        write_partitions_tuplex(req["outdir"], result.partitions,
                                backend=backend)
    resp = {"ok": True,
            "rows": sum(p.num_rows for p in result.partitions),
            "metrics": result.metrics,
            "exceptions": result.exceptions,
            "failure_log": list(backend.failure_log[fl_snap:])}
    emit("done", task=req.get("task"), rows=resp["rows"],
         exceptions=len(result.exceptions),
         wall_s=round(time.time() - t_start, 3))
    _write_response(req_path, resp)
    return resp


def _write_response(req_path: str, resp: dict) -> None:
    task_dir = os.path.dirname(os.path.abspath(req_path))
    tmp = os.path.join(task_dir, ".response.tmp")
    with open(tmp, "wb") as fp:
        pickle.dump(resp, fp)
    os.replace(tmp, os.path.join(task_dir, "response.pkl"))


def serve() -> int:
    """Warm-worker loop: one request path per stdin line; 'EXIT' quits.

    Completion AND liveness are signalled solely by the atomic
    response.pkl write — the driver redirects this process's stdout into
    its log file and never reads it, so the 'READY'/'OK' lines below are
    log breadcrumbs, not a protocol (ADVICE r5)."""
    _set_platform()
    from ..utils.lru import LruDict

    # one backend per option fingerprint, LRU-bounded: a multi-tenant
    # driver interleaving option sets keeps each tenant's warm backend
    # (and its traced executables) instead of thrashing on every task
    try:
        cap = max(1, int(os.environ.get("TUPLEX_WORKER_BACKENDS", "4")))
    except ValueError:
        cap = 4
    backends = LruDict(cap)
    print("READY", flush=True)
    for line in sys.stdin:
        req_path = line.strip()
        if not req_path:
            continue
        if req_path == "EXIT":
            break
        try:
            run_task(req_path, backends)
            print(f"OK {req_path}", flush=True)
        except Exception as e:  # task failure must not kill the worker
            try:
                _write_response(req_path, {
                    "ok": False,
                    "error": f"{type(e).__name__}: {e}"})
            except OSError:
                pass
            import traceback

            traceback.print_exc(file=sys.stderr)
            print(f"ERR {req_path}", flush=True)
    return 0


def main(argv: list[str]) -> int:
    if argv == ["--serve"]:
        return serve()
    if len(argv) != 1:
        print("usage: python -m tuplex_tpu.exec.worker "
              "(<request.pkl> | --serve)", file=sys.stderr)
        return 2
    _set_platform()
    run_task(argv[0])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
