"""Public Context — the engine entry point.

API parity with the reference's Python Context (reference:
python/tuplex/context.py:50 — options merge, parallelize/csv/text entry
points; core/include/Context.h:43). There is no binding layer: planning and
execution are Python-driven, the hot path is XLA-compiled.
"""

from __future__ import annotations

from typing import Any, Mapping, Optional, Sequence

from ..core import typesys as T
from ..core.errors import TuplexException
from ..core.options import ContextOptions
from ..exec.local import LocalBackend
from ..plan import logical as L
from ..runtime import columns as C
from .metrics import Metrics


class Context:
    def __init__(self, conf: Mapping[str, Any] | str | None = None, **kwargs):
        self.options_store = ContextOptions(conf if not isinstance(conf, str)
                                            else None, **kwargs)
        if isinstance(conf, str):
            self.options_store.update(conf)
        from ..runtime import tracing

        if self.options_store.get_bool("tuplex.tpu.trace", False):
            # span tracing is process-wide (spans cross backend/compile-
            # pool threads); the option turns it on, never off — another
            # live Context (or TUPLEX_TRACE=1) may also depend on it
            tracing.enable(True)
        # the time a first job spends before its `job` span opens
        with tracing.span("context:init", "job") as _sp:
            self._init_planes()
            _sp.set("backend", type(self.backend).__name__) \
               .set("devices", getattr(self.backend, "n_devices", 1))

    def _init_planes(self) -> None:
        """The rest of construction: process-wide gates, the backend, the
        recorder (inside the `context:init` span)."""
        # sample-free specialization gate (compiler/typeinfer.py): like
        # tracing, the flag is process-wide — planning code paths have no
        # Context handle at schema-inference depth. TUPLEX_STATIC_TYPES
        # env (checked inside typeinfer.enabled) overrides either way.
        from ..compiler import typeinfer as _ti

        _ti.set_enabled(self.options_store.get_bool(
            "tuplex.tpu.staticTypes", True))
        # device-plane cost attribution (runtime/devprof): same process-
        # wide on-only semantics as tracing/telemetry; TUPLEX_DEVPROF=0
        # is the env kill switch that wins over everything
        from ..runtime import devprof as _dp

        _dp.apply_options(self.options_store)
        # exception-plane observability (runtime/excprof): per-code
        # fallback attribution + drift detection; TUPLEX_EXCPROF=0 is
        # the env kill switch that wins over everything
        from ..runtime import excprof as _ex

        _ex.apply_options(self.options_store)
        # jaxpr-plane static vetting (compiler/graphlint): pre-submission
        # compile-hazard analysis; TUPLEX_GRAPHLINT=0 is the env kill
        # switch that wins over everything
        from ..compiler import graphlint as _gl

        _gl.apply_options(self.options_store)
        self.backend = self._make_backend()
        self.metrics = Metrics()
        from ..history import JobRecorder

        webui = self.options_store.get_bool("tuplex.webui", False)
        self.recorder = JobRecorder(
            self.options_store.get_str("tuplex.logDir", "."),
            enabled=webui or
            self.options_store.get_bool("tuplex.webui.enable"),
            exception_display_limit=self.options_store.get_int(
                "tuplex.webui.exceptionDisplayLimit", 5))
        self._webui_server = None
        self._webui_url = None
        if webui:
            # live dashboard autostart (reference: ensure_webui spawning
            # mongod + gunicorn; here one stdlib http thread)
            from ..history.recorder import start_server

            try:
                self._webui_server, self._webui_url = start_server(
                    self.options_store.get_str("tuplex.logDir", "."),
                    port=self.options_store.get_int("tuplex.webui.port", 0))
            except OSError as e:
                from ..utils.logging import get_logger

                get_logger("webui").warning("webui autostart failed: %s", e)
                self._webui_url = ""   # uiWebURL: nothing is serving
        if self.options_store.get_bool("tuplex.redirectToPythonLogging"):
            from ..utils.logging import redirect_to_python_logging

            redirect_to_python_logging(True)

    def _make_backend(self):
        name = self.options_store.get_str("tuplex.backend", "local")
        if name in ("local", "tpu"):
            return LocalBackend(self.options_store)
        if name == "multihost":
            from ..exec.multihost import MultiHostBackend

            return MultiHostBackend(self.options_store)
        if name in ("serverless", "lambda"):
            from ..exec.serverless import ServerlessBackend

            return ServerlessBackend(self.options_store)
        raise TuplexException(f"unknown backend {name!r}")

    # ------------------------------------------------------------------
    def parallelize(self, value_list: Sequence[Any],
                    columns: Optional[Sequence[str]] = None,
                    schema: Optional[T.RowType] = None,
                    auto_unpack: bool = True) -> "DataSet":
        """Create a DataSet from python values (reference: context.py:246
        parallelize → PythonContext.cc:823-919 fast transfer + fallback
        partitions for non-conforming rows). `auto_unpack=False` keeps dict
        rows as boxed dictionary values instead of spreading them into
        named columns."""
        from .dataset import DataSet

        data = list(value_list)
        if not data:
            raise TuplexException("parallelize: empty input")
        max_rows = self.options_store.get_int(
            "tuplex.sample.maxDetectionRows", 1000)
        threshold = self.options_store.get_float(
            "tuplex.normalcaseThreshold", 0.9)
        if schema is None:
            schema = _infer_row_schema(
                data[:max_rows], columns, threshold,
                auto_unpack=auto_unpack)
        elif columns:
            schema = T.row_of(columns, schema.types)

        if auto_unpack and C.user_columns(schema) and \
                any(isinstance(v, dict) for v in data[:8]):
            # dict rows were auto-unpacked into named columns: convert values
            # (rows missing keys stay boxed and go to the fallback path)
            keys = list(schema.columns)
            data = [
                tuple(d[k] for k in keys)
                if isinstance(d, dict) and set(d.keys()) == set(keys) else d
                for d in data
            ]

        op = L.ParallelizeOperator(data, schema, sample_size=max_rows)
        return DataSet(self, op)

    def csv(self, pattern: str, columns=None, header=None, delimiter=None,
            quotechar: Optional[str] = None, null_values=None,
            type_hints=None) -> "DataSet":
        from ..io.csvsource import make_csv_operator
        from .dataset import DataSet

        op = make_csv_operator(self.options_store, pattern, columns=columns,
                               header=header, delimiter=delimiter,
                               quotechar=quotechar, type_hints=type_hints,
                               null_values=null_values)
        return DataSet(self, op)

    def text(self, pattern: str, null_values=None) -> "DataSet":
        """One row per line; lines equal to a null value load as None
        (reference: context.py text → FileInputOperator text mode)."""
        from ..io.csvsource import make_text_operator
        from .dataset import DataSet

        return DataSet(self, make_text_operator(self.options_store, pattern,
                                                null_values=null_values))

    def orc(self, pattern: str, columns=None) -> "DataSet":
        from ..io.orcsource import make_orc_operator
        from .dataset import DataSet

        return DataSet(self, make_orc_operator(self.options_store, pattern,
                                               columns=columns))

    def tuplexfile(self, path: str) -> "DataSet":
        """Read a dataset written by DataSet.totuplex — the engine's native
        binary partition format; columnar leaves reload without sniffing or
        decoding (reference: FileFormat::OUTFMT_TUPLEX)."""
        from ..io.tuplexfmt import make_tuplex_operator
        from .dataset import DataSet

        return DataSet(self, make_tuplex_operator(self.options_store, path))

    def options(self, nested: bool = False) -> dict:
        flat = self.options_store.as_dict()
        if not nested:
            return flat
        out: dict = {}
        for k, v in flat.items():
            cur = out
            ks = k.split(".")
            for piece in ks[:-1]:
                nxt = cur.setdefault(piece, {})
                if not isinstance(nxt, dict):   # leaf-then-group collision
                    nxt = cur[piece] = {"": nxt}
                cur = nxt
            if isinstance(cur.get(ks[-1]), dict):
                cur[ks[-1]][""] = v             # group-then-leaf collision
            else:
                cur[ks[-1]] = v
        return out

    def optionsToYAML(self, file_path: str = "config.yaml") -> None:
        with open(file_path, "w") as fp:
            for k, v in sorted(self.options_store.as_dict().items()):
                fp.write(f"{k}: {v}\n")

    # filesystem helpers (reference: context.py ls/cp/rm via VFS)
    def ls(self, pattern: str) -> list[str]:
        from ..io.vfs import VirtualFileSystem

        return VirtualFileSystem.ls(pattern)

    def cp(self, pattern: str, target_uri: str) -> None:
        from ..io.vfs import VirtualFileSystem

        VirtualFileSystem.cp(pattern, target_uri)

    def rm(self, pattern: str) -> None:
        from ..io.vfs import VirtualFileSystem

        VirtualFileSystem.rm(pattern)

    # ------------------------------------------------------------------
    def job_service(self):
        """The lazily-created in-process JobService (serve/) sharing this
        context's options and warm device. One per Context; closed with
        it."""
        svc = getattr(self, "_job_service", None)
        if svc is None:
            from ..serve import JobService

            svc = self._job_service = JobService(
                self.options_store, recorder=self.recorder)
        return svc

    def submit(self, dataset, name: str = "job", tenant: str = "default",
               memory_budget=None, weight=None):
        """Submit a DataSet pipeline to the job service instead of running
        it inline: returns a JobHandle immediately; the service fair-shares
        stage dispatches across all submitted jobs on the warm device
        (serve/service.py). ``memory_budget`` (bytes or a "128MB" string)
        bounds the job's resident partitions — past it the job spills via
        the LRU evictor rather than pressuring other tenants."""
        from ..core.options import _size_to_bytes
        from ..serve import request_from_dataset

        budget = None if memory_budget is None \
            else _size_to_bytes(memory_budget)
        req = request_from_dataset(dataset, name=name, tenant=tenant,
                                   memory_budget=budget, weight=weight)
        return self.job_service().submit(req)

    def uiWebURL(self) -> str:
        if self._webui_url is not None:
            return self._webui_url   # "" when autostart failed: not serving
        host = self.options_store.get_str("tuplex.webui.url", "localhost")
        port = self.options_store.get_str("tuplex.webui.port", "5000")
        return f"http://{host}:{port}"

    def close(self) -> None:
        """Release context resources (the autostarted webui server's socket
        and thread; warm serverless workers). Safe to call repeatedly."""
        svc = getattr(self, "_job_service", None)
        if svc is not None:
            try:
                svc.close()
            except Exception:
                pass
            self._job_service = None
        be = getattr(self, "backend", None)
        if be is not None and hasattr(be, "close"):
            try:
                be.close()
            except Exception:
                pass
        if self._webui_server is not None:
            try:
                self._webui_server.shutdown()
                self._webui_server.server_close()
            except Exception:
                pass
            self._webui_server = None
            self._webui_url = ""   # nothing serving anymore

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


def _infer_row_schema(sample: list, columns, threshold: float,
                      auto_unpack: bool = True) -> T.RowType:
    """Column-wise normal-case speculation (reference:
    PythonContext.cc:1023 inferType — majority type over the sample)."""
    dicts = auto_unpack and all(isinstance(v, dict) for v in sample)
    if dicts and sample:
        # auto-unpack string-keyed dicts into named columns (reference:
        # strDictParallelize, PythonContext.cc:617)
        keys = list(sample[0].keys())
        if all(list(d.keys()) == keys for d in sample) and \
                all(isinstance(k, str) for k in keys):
            types = [T.normal_case_type([d[k] for d in sample], threshold)[0]
                     for k in keys]
            return T.row_of(keys, types)
    tuples = [v for v in sample if isinstance(v, tuple)]
    if tuples and len(tuples) >= threshold * len(sample):
        k = len(tuples[0])
        if all(len(t) == k for t in tuples):
            types = []
            for ci in range(k):
                vals = [t[ci] for t in tuples]
                nc, _, _ = T.normal_case_type(vals, threshold)
                types.append(nc)
            names = list(columns) if columns else [f"_{i}" for i in range(k)]
            if len(names) != k:
                raise TuplexException(
                    f"{k} columns in data but {len(names)} names given")
            return T.row_of(names, types)
    nc, _, _ = T.normal_case_type(sample, threshold)
    names = list(columns) if columns else ["_0"]
    return T.row_of(names[:1], [nc])


class LambdaContext(Context):
    """Distributed-by-default Context (reference: python/tuplex/__init__.py
    exports LambdaContext preset to the serverless backend). Now that the
    serverless fan-out exists (`exec/serverless.py` — the AWSLambdaBackend
    analog) it is the honest default here too; pass
    ``tuplex.backend=multihost`` for SPMD-mesh distribution instead."""

    def __init__(self, conf=None, **kwargs):
        merged = dict(conf or {})
        merged.setdefault("tuplex.backend", "serverless")
        super().__init__(merged, **kwargs)
