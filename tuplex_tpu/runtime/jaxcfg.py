"""Central jax import + config. Import jax ONLY through here inside the
framework so x64 is enabled before any trace happens.

Python ints are i64 in the reference's type system (TypeSystem.h); on TPU
i64 is emulated but the hot arithmetic is mostly i32-safe — the emitter
narrows where value ranges allow (future work, tuplex.tpu.* options).
"""

import os

import jax

jax.config.update("jax_enable_x64", True)

def _host_tag() -> str:
    """Tag for this host's CPU. XLA:CPU AOT results encode target machine
    features; loading artifacts compiled on a different machine warns
    about SIGILL risk (observed with a shared cache dir:
    +prefer-no-scatter/+avx512* mismatches), so the tag is part of every
    AOT fingerprint (aot_platform_tag)."""
    import hashlib
    import platform

    tag = platform.machine()
    try:
        with open("/proc/cpuinfo") as fp:
            for line in fp:
                if line.startswith("flags"):
                    tag += hashlib.sha256(line.encode()).hexdigest()[:8]
                    break
    except OSError:
        pass
    return tag


# Everything a run keeps of its compiles (XLA's persistent cache, the AOT
# executable store) lives under ONE fixed directory inside the checkout,
# never under ~ or a per-run name: the path is part of jax's cache key,
# and a run must be reproducible from the tree it ran in.
STATE_ROOT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".tuplex_cache")


def state_dir(name: str) -> str:
    """`STATE_ROOT/<name>`, created; "" when it cannot be."""
    d = os.path.join(STATE_ROOT, name)
    try:
        os.makedirs(d, exist_ok=True)
    except OSError:
        return ""
    return d


# Persistent XLA compile cache: fused-stage executables are expensive to
# build but perfectly cacheable — identical HLO hits the on-disk cache in
# milliseconds across processes. Where JAX_COMPILATION_CACHE_DIR is set
# jax has already read it and the directory stays the caller's choice;
# otherwise the cache goes to the fixed directory above.
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    _cache_dir = state_dir("xla")
    if _cache_dir:
        jax.config.update("jax_compilation_cache_dir", _cache_dir)
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)

import jax.numpy as jnp  # noqa: E402
from jax import lax  # noqa: E402

__all__ = ["jax", "jnp", "lax", "fusion_barriers_enabled"]


def fusion_barriers_enabled() -> bool:
    """Whether stage traces insert lax.optimization_barrier between operators
    / statements / error-lattice updates.

    XLA-CPU's producer fusion inlines whole UDF bodies into one kLoop fusion
    that RECOMPUTES [B, W] string intermediates per output element (measured
    24x on Zillow extractPrice), so barriers are load-bearing there. XLA-TPU
    fuses loop nests without that pathology and barriers only lengthen its
    compile, so they default off everywhere except CPU. Override:
    TUPLEX_FUSION_BARRIERS=0/1."""
    import os

    mode = os.environ.get("TUPLEX_FUSION_BARRIERS", "auto")
    if mode in ("0", "1"):
        return mode == "1"
    return jax.default_backend() == "cpu"


def device_handoff_enabled(consumer: str = "stage") -> bool:
    """Whether intermediate stage outputs keep a device-resident gathered
    view for downstream re-staging (skips host pad/copy + H2D — the analog
    of the reference passing hash intermediates by pointer as stage
    globals, LocalBackend.cc:903-908). Default: off on CPU (host staging IS
    device memory there; the extra device gather would be pure overhead),
    on everywhere else.

    `consumer` names WHO drains the view — "stage" (a downstream
    TransformStage re-stages it), "join" (the probe side of a JoinStage
    gathers from it), or "agg" (an AggregateStage evaluates fold exprs over
    it). Round 5 gated joins and aggregates off entirely, which is exactly
    the boundary that made q19/flights/nyc311 round-trip per stage; the
    per-consumer knobs exist so a regressing consumer can be switched off
    without losing the others. TUPLEX_DEVICE_HANDOFF=0/1 overrides all
    consumers (tests force it on under the CPU platform);
    TUPLEX_DEVICE_HANDOFF_STAGE / _JOIN / _AGG=0/1 override one."""
    import os

    per = os.environ.get(f"TUPLEX_DEVICE_HANDOFF_{consumer.upper()}")
    if per in ("0", "1"):
        return per == "1"
    mode = os.environ.get("TUPLEX_DEVICE_HANDOFF", "auto")
    if mode in ("0", "1"):
        return mode == "1"
    return jax.default_backend() != "cpu"


def varlen_wire_enabled() -> bool:
    """Whether packed stage outputs ship str leaves as a varlen segment
    (per-row lengths + contiguous payload of ACTUAL bytes) instead of the
    zero-padded [B, W] matrices. The padded matrices are ~170 B/row on
    zillow against ~30 B of real content — shipping content-sized payloads
    is the same offsets+payload
    layout the reference serializer uses on disk (Serializer.h:104-138)
    applied to the transfer wire. Only meaningful where packing is active
    (the varlen segment rides PackedOuts). TUPLEX_VARLEN_WIRE=0/1
    overrides; default on."""
    import os

    mode = os.environ.get("TUPLEX_VARLEN_WIRE", "auto")
    if mode in ("0", "1"):
        return mode == "1"
    return True


def device_handoff_budget_bytes() -> int:
    """Cap on device memory pinned by handoff views per stage. Views are
    one-shot (released at consumption), but ALL of a stage's outputs hold
    views until the next stage drains them — without a cap a large
    intermediate dataset would pin O(dataset) HBM. Default: 25% of the
    device's reported bytes_limit, else 1 GiB. TUPLEX_DEVICE_HANDOFF_MB
    overrides."""
    import os

    mb = os.environ.get("TUPLEX_DEVICE_HANDOFF_MB")
    if mb is not None:
        try:
            return int(float(mb) * (1 << 20))
        except ValueError:
            pass
    try:
        stats = jax.devices()[0].memory_stats() or {}
        limit = int(stats.get("bytes_limit", 0))
        if limit > 0:
            return limit // 4
    except Exception:
        pass
    return 1 << 30


def stmt_barriers_enabled() -> bool:
    """Statement-level barriers inside UDF bodies (finer than the per-
    operator barriers in the stage loop). Separately switchable so the
    granularity tradeoff (materialized bandwidth vs recompute) can be
    tuned per platform. TUPLEX_STMT_BARRIERS=0/1 overrides."""
    import os

    mode = os.environ.get("TUPLEX_STMT_BARRIERS", "auto")
    if mode in ("0", "1"):
        return mode == "1"
    return fusion_barriers_enabled()


def shard_map_compat(fn, mesh, in_specs, out_specs, check: bool = False):
    """``jax.shard_map`` with this repo's defaults. Import jax's shard_map
    ONLY through here (same rule as the jax import itself)."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)


def aot_cache_enabled() -> bool:
    """Content-addressed AOT executable reuse (exec/compilequeue.py): stage
    executables serialize to disk keyed on (canonical jaxpr fingerprint,
    platform/ISA, avals, donation/packing flags, mesh epoch) so a second
    process re-running the same pipeline deserializes instead of compiling.
    This sits ABOVE jax's own persistent compilation cache: that one still
    re-runs the XLA pipeline front-end per process; this one skips the
    compile call entirely (the hit/miss counters in compilequeue.STATS are
    the proof). TUPLEX_AOT_CACHE=0 disables; =<path> relocates the store."""
    return os.environ.get("TUPLEX_AOT_CACHE", "") != "0"


def aot_cache_dir() -> str:
    """On-disk artifact directory for serialized stage executables: a
    fixed sibling of the XLA cache, or TUPLEX_AOT_CACHE=<path>. Artifacts
    of different hosts/ISAs may share it — the host tag is part of every
    fingerprint (aot_platform_tag)."""
    v = os.environ.get("TUPLEX_AOT_CACHE", "")
    if v == "0":
        return ""
    if not v:
        return state_dir("aot")
    try:
        os.makedirs(v, exist_ok=True)
    except OSError:
        return ""
    return v


def aot_platform_tag() -> str:
    """Platform component of the AOT fingerprint: effective backend +
    host-ISA tag + x64 mode + jax version. Anything that changes what a
    compiled executable MEANS must appear here."""
    return "/".join((jax.default_backend(), _host_tag(),
                     f"x64={int(bool(jax.config.jax_enable_x64))}",
                     f"jax={jax.__version__}"))


def f64_is_f32_pair() -> bool:
    """Whether a float64 on the device a trace will run on is a PAIR of
    float32 (hi, lo = RN24(x), RN24(x - hi)) and not IEEE binary64: so it
    is on a TPU (~49 significant bits, float32's exponent range, and a
    division that is off by up to 4e-15 relative — probed on a v5e, PR 24).
    Kernels that must hand back the value CPython would (ops/strings
    parse_f64) then build the pair with integer arithmetic, which the TPU
    does exactly. Reads the device a trace is pinned to first (exec/local
    ``_CpuJit`` traces host-CPU executables inside a TPU process)."""
    dev = jax.config.jax_default_device
    platform = getattr(dev, "platform", dev) or jax.default_backend()
    return platform == "tpu"


def donation_enabled() -> bool:
    """Whether stage dispatch donates its input device buffers to XLA
    (halves per-stage HBM residency: the staged input is dead the moment
    the kernel reads it — every consumer re-stages from host leaves or a
    one-shot handoff view). Off on CPU, where XLA does not support
    donation and would warn per call. TUPLEX_DONATE=0/1 overrides (tests
    force it on under the CPU platform to exercise the path)."""
    import os

    mode = os.environ.get("TUPLEX_DONATE", "auto")
    if mode in ("0", "1"):
        return mode == "1"
    return jax.default_backend() not in ("cpu",)
