"""Worker process for the real-multi-process jax.distributed test
(tests/test_multiprocess.py). NOT a pytest module.

Each process: init jax.distributed against a localhost coordinator, build a
multihost Context over the GLOBAL mesh (2 procs x 2 virtual CPU devices),
run the pipelines SPMD, and dump collected results to a pickle for the
parent to compare against the single-process reference (reference analog:
AWSLambdaBackend correctness is only provable against real AWS,
AWSLambdaBackend.cc:254-330 — here the control plane is jax.distributed
and it IS locally testable).
"""
import os
import pickle
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    pid = int(sys.argv[1])
    nproc = int(sys.argv[2])
    port = sys.argv[3]
    data_csv = sys.argv[4]
    out_path = sys.argv[5]

    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import tuplex_tpu
    from tuplex_tpu.models import nyc311

    os.environ["TUPLEX_COORDINATOR"] = f"localhost:{port}"
    os.environ["TUPLEX_NUM_PROCESSES"] = str(nproc)
    os.environ["TUPLEX_PROCESS_ID"] = str(pid)
    from tuplex_tpu.exec.deploy import init_from_env, preflight

    init_from_env()     # the deploy-helper path (reference: distributed.py)
    preflight(expected_processes=nproc, expected_devices_per_process=2)

    ctx = tuplex_tpu.Context({
        "tuplex.backend": "multihost",
        "tuplex.scratchDir": f"{out_path}.scratch{pid}",
    })

    results = {}
    results["nyc311"] = nyc311.build_pipeline(ctx, data_csv).collect()
    # record whether the csv source really took the host-sharded path
    src_op = ctx.csv(data_csv)._op
    while src_op.parents:
        src_op = src_op.parent
    results["nyc311_sharded"] = bool(src_op._host_sharded(ctx))

    # host-sharded TEXT reads: each process reads ONLY its byte range of
    # the log file; the global batch assembles from per-host blocks and
    # interpreter rows (malformed lines etc.) exchange over DCN
    from tuplex_tpu.io.vfs import VirtualFileSystem
    from tuplex_tpu.models import logs as logs_model

    log_txt = data_csv + ".logs.txt"
    if pid == 0 and not os.path.exists(log_txt):
        # write-then-rename: the other process's existence barrier must
        # never observe a partially written file
        logs_model.generate_log(log_txt + ".tmp", 3000)
        os.rename(log_txt + ".tmp", log_txt)
    import time as _t
    for _ in range(200):
        if os.path.exists(log_txt):
            break
        _t.sleep(0.05)
    else:
        raise RuntimeError(f"log file never appeared: {log_txt}")
    assert VirtualFileSystem.file_size(log_txt) > 0
    results["logs"] = logs_model.build_pipeline(
        ctx.text(log_txt), "strip").collect()

    # quoted CSV: the EXACT quote gate must fall back to whole reads and
    # still produce correct (quote-aware) results
    qcsv = data_csv + ".quoted.csv"
    if pid == 0 and not os.path.exists(qcsv):
        with open(qcsv + ".tmp", "w") as fp:
            fp.write("a,b\n")
            for i in range(500):
                fp.write(f'"x,{i}",{i}\n')
        os.rename(qcsv + ".tmp", qcsv)
    for _ in range(200):
        if os.path.exists(qcsv):
            break
        _t.sleep(0.05)
    else:
        raise RuntimeError("quoted csv never appeared")
    results["quoted"] = ctx.csv(qcsv).map(
        lambda x: (x["a"], x["b"] * 2)).collect()

    # psum-combined aggregate over DCN
    data = [(float(i % 50) / 100, float(i % 7)) for i in range(4096)]
    results["agg"] = (ctx.parallelize(data, columns=["disc", "price"])
                      .filter(lambda x: x["disc"] > 0.05)
                      .aggregate(lambda a, b: a + b,
                                 lambda a, x: a + x["price"] * x["disc"],
                                 0.0)
                      .collect())

    # mesh broadcast join (build replicated, probe row-sharded)
    left = ctx.parallelize([(i % 37, i) for i in range(2048)],
                           columns=["k", "v"])
    right = ctx.parallelize([(i, i * 10) for i in range(30)],
                            columns=["k", "w"])
    results["join"] = sorted(left.join(right, "k", "k").collect())

    with open(f"{out_path}.p{pid}", "wb") as fp:
        pickle.dump(results, fp)
    print(f"[p{pid}] OK", flush=True)


if __name__ == "__main__":
    main()
