"""Single-buffer transfer packing for stage dispatch.

A PJRT data plane pays a fixed per-buffer cost in both directions:
staging the zillow batch is ~60 leaf arrays and fetching its result ~43
(what that costs on this machine is not measured — the policy dates from a
transport where it dominated). Packing every leaf into ONE uint8 buffer
per direction —
with the unpack/pack bitcasts fused into the stage executable — collapses
those per-buffer round-trips into one H2D and one D2H.

Reference analog: the C++ runtime ships whole partitions as single memory
blocks (tuplex/core/include/Partition.h) rather than per-column buffers;
this is the same idea applied to the PJRT transfer layer.

Host side packs with numpy views (memcpy only); device side slices +
bitcast_convert_type inside the jit, so XLA sees static offsets and the
donated input buffer can be reused for the output.
"""

from __future__ import annotations

import numpy as np

from .jaxcfg import jax, jnp

# segment alignment inside the packed buffer: large enough that every
# element-typed view of a segment is aligned (max itemsize 8; 64 also
# keeps cache-line alignment), small enough that a many-leaf stage does
# not bleed KBs of padding per partition (512 cost ~18 KB/partition on
# zillow's ~35 output leaves)
_ALIGN = 64


def packing_enabled() -> bool:
    """Default: pack on accelerator backends (the per-buffer RPC tax is a
    PCIe/transport property); CPU 'transfers' are pointer handoffs where the
    extra memcpy is pure loss. TUPLEX_PACK_TRANSFERS=0/1 overrides (tests
    force it on under CPU for parity coverage)."""
    import os

    mode = os.environ.get("TUPLEX_PACK_TRANSFERS", "auto")
    if mode in ("0", "1"):
        return mode == "1"
    return jax.default_backend() != "cpu"


def _pad(nb: int) -> int:
    return -(-nb // _ALIGN) * _ALIGN


# the varlen payload leaves the executable as this many equal buffers at
# most (one where it is small): the host needs payload[:total] alone, and
# a WHOLE buffer is what a transfer fetches without an executable. A slice
# on the device is one, and a chip runs its executables in the order they
# were enqueued — behind every dispatch in flight, so the fetch of one
# partition would wait out the chip's run of the next two
_VCHUNKS = 16
_VCHUNK_MIN = 1 << 16


def _payload_chunking(nbytes: int) -> tuple:
    """(chunks, bytes a chunk) of a varlen payload of `nbytes` capacity."""
    n = min(_VCHUNKS, max(1, nbytes // _VCHUNK_MIN))
    return n, max(_pad(-(-nbytes // n)), _ALIGN)


def _packable(dtype) -> bool:
    """Dtypes the in-executable bitcasts handle on every backend. 64-bit
    ints split into u32 halves arithmetically (the XLA-TPU x64 legalizer
    has no rule for 64-bit bitcast-convert inside large stage graphs, and
    f64<->int bitcasts fail outright on the current TPU stack — probed on
    the live chip); f64 and anything exotic transfer per-leaf instead."""
    return np.dtype(dtype) in (np.dtype(np.uint8), np.dtype(np.bool_),
                               np.dtype(np.int8), np.dtype(np.int16),
                               np.dtype(np.uint16), np.dtype(np.int32),
                               np.dtype(np.uint32), np.dtype(np.float32),
                               np.dtype(np.int64), np.dtype(np.uint64))


# wire-dtype markers beyond plain numpy dtype strs:
#   "b1"     1-D bool bitpacked little-endian, 8 rows/byte (both directions)
#   "lo4i"   i64 shipped as its low u32 word; high words ride the varlen
#   "lo4u"   u64 same — payload carries (rare) rows whose high word isn't
#            the low word's sign/zero extension (output direction only)
#   "pb<N>"  '#rowidx' as a survivor bitmap over the padded input size N:
#            the compaction contract (physical.py) keeps the indices
#            ascending+unique with sentinel N for dead tail slots, so a
#            bit per INPUT row reconstructs them exactly (output only)
_BITS = "b1"
_LO32 = {"<i8": "lo4i", "<u8": "lo4u"}


def _wire_nbytes(shape, wdt: str) -> int:
    n = int(np.prod(shape)) if shape else 1
    if wdt == _BITS:
        return (n + 7) // 8
    if wdt in ("lo4i", "lo4u"):
        return n * 4
    if wdt.startswith("pb"):
        return (int(wdt[2:]) + 7) // 8
    return n * np.dtype(wdt).itemsize


def _wire_dtype(k: str, dtype, arrays, check_values: bool = False) -> str:
    """Transfer dtype (str, possibly a marker) for a leaf.

    * 1-D bool leaves bitpack 8 rows/byte ('#keep', '#rowvalid', Option
      validity — an 8x cut on every boolean lattice column).
    * A '#len' column is bounded by its sibling byte matrix's padded
      width, so it narrows to u16 (or u8 when the width fits a byte) and
      re-widens on arrival. ('#err' is NOT narrowed: it packs
      class|position<<8, and a stage of 256 operators or more exceeds
      u16.)
    * '#rowidx' values are bounded by the padded INPUT size (sentinel
      included), visible statically as '#err'.shape — u16 when it fits.

    check_values (host pack path only — device values are traced):
    the len<=padded-width invariant is enforced nowhere upstream, so a
    '*#len' leaf carrying values past the narrowed range (or a negative
    sentinel) would silently wrap on the wire; such leaves fall back to
    their declared dtype (ADVICE round 5)."""
    dt = np.dtype(dtype)
    a = arrays.get(k)
    if dt == np.dtype(np.bool_) and getattr(a, "ndim", 0) == 1:
        return _BITS
    if dt == np.dtype(np.int32) and k.endswith("#len"):
        sib = arrays.get(k[:-4] + "#bytes")
        if sib is not None and getattr(sib, "ndim", 0) == 2 \
                and sib.shape[1] < (1 << 16):
            narrow = np.uint8 if sib.shape[1] <= 0xFF else np.uint16
            if check_values:
                av = np.asarray(a)
                if av.size and (int(av.max()) > int(np.iinfo(narrow).max)
                                or int(av.min()) < 0):
                    return dt.str
            return np.dtype(narrow).str
    if dt == np.dtype(np.int32) and k == "#rowidx":
        err = arrays.get("#err")
        b_in = err.shape[0] if err is not None \
            and getattr(err, "ndim", 0) == 1 else None
        if b_in is not None and not check_values:
            # device direction: the compaction contract (ascending,
            # unique, sentinel=b_in) is structural — a bit per input row
            return f"pb{b_in}"
        if b_in is not None and b_in < (1 << 16):
            av = np.asarray(a)
            if not av.size or (int(av.max()) < (1 << 16)
                               and int(av.min()) >= 0):
                return np.dtype(np.uint16).str
    return dt.str


def _host_spec(arrays: dict, check_values: bool = True):
    """Deterministic layout: (key, shape, dtype_str, offset, wire_nbytes,
    wire_dtype_str). ``check_values=False`` computes the layout from
    shapes/dtypes alone (ShapeDtypeStruct avals work) — the PREDICTED spec
    the AOT prewarm compiles against; it matches the dispatch-time spec
    whenever the '#len' narrowing invariant holds (the normal case — a
    violating partition just compiles its own wide-layout variant)."""
    spec = []
    off = 0
    for k in sorted(arrays):
        a = arrays[k]
        if not _packable(a.dtype):
            continue
        wd = _wire_dtype(k, a.dtype, arrays, check_values=check_values)
        nb = _wire_nbytes(a.shape, wd)
        spec.append((k, tuple(a.shape), np.dtype(a.dtype).str, off, nb, wd))
        off += _pad(nb)
    return tuple(spec), off


def _pack_host(arrays: dict, spec, total: int) -> np.ndarray:
    buf = np.zeros(total, dtype=np.uint8)
    for k, shape, dt, off, nb, wdt in spec:
        if not nb:
            continue
        a = np.ascontiguousarray(arrays[k])
        if wdt == _BITS:
            bits = np.packbits(a.astype(np.bool_).reshape(-1),
                               bitorder="little")
            buf[off:off + nb] = bits
            continue
        if wdt != dt:
            a = np.ascontiguousarray(a.astype(np.dtype(wdt)))
        buf[off:off + nb] = a.view(np.uint8).reshape(-1)
    return buf


def _unpack_host(buf: np.ndarray, spec) -> dict:
    out = {}
    for k, shape, dt, off, nb, wdt in spec:
        dtype = np.dtype(dt)
        n = int(np.prod(shape)) if shape else 1
        if not nb:
            out[k] = np.zeros(shape, dtype=dtype)
            continue
        if wdt == _BITS:
            seg = np.frombuffer(buf, dtype=np.uint8, count=nb, offset=off)
            out[k] = np.unpackbits(seg, bitorder="little")[:n] \
                .astype(np.bool_).reshape(shape)
            continue
        if wdt in ("lo4i", "lo4u"):
            lo = np.frombuffer(buf, dtype=np.uint32, count=n, offset=off)
            # sign/zero-extend the low word; rows whose high word differs
            # are patched from the varlen payload (_unpack_varlen)
            out[k] = (lo.astype(np.int32).astype(np.int64)
                      if wdt == "lo4i"
                      else lo.astype(np.uint64)).astype(dtype) \
                .reshape(shape)
            continue
        if wdt.startswith("pb"):
            b_in = int(wdt[2:])
            seg = np.frombuffer(buf, dtype=np.uint8, count=nb, offset=off)
            pos = np.nonzero(
                np.unpackbits(seg, bitorder="little")[:b_in])[0]
            arr = np.full(n, b_in, dtype=dtype)   # sentinel tail slots
            arr[:min(len(pos), n)] = pos[:n]
            out[k] = arr.reshape(shape)
            continue
        wdtype = np.dtype(wdt)
        # zero-copy views: offsets are _ALIGN-ed so every element aligns
        arr = np.frombuffer(buf, dtype=wdtype,
                            count=nb // wdtype.itemsize,
                            offset=off).reshape(shape)
        out[k] = arr.astype(dtype) if wdtype != dtype else arr
    return out


def _device_unpack(buf, spec):
    """Traced: one u8 buffer -> dict of typed arrays (static slices +
    bitcasts; XLA fuses these into the stage executable). 64-bit ints
    combine from u32 halves arithmetically — no 64-bit bitcast reaches
    the TPU x64 legalizer.

    Every leaf's byte range is MATERIALIZED (optimization barrier) before
    it is reshaped: left to itself XLA:TPU merges all the slices of the
    one wire buffer into a single multi-output fusion whose compile time
    and code size grow superlinearly with the number of leaves — zillow's
    33 leaves at a 106,496-row bucket: 279.5 s and 176 MB of code without
    the barrier, 2.0 s and 8 MB with it (compile rehearsal for a v5e,
    PR 24)."""
    out = {}
    segs = jax.lax.optimization_barrier(
        tuple(buf[off:off + nb] for _k, _s, _d, off, nb, _w in spec))
    for (k, shape, dt, off, nb, wdt), seg in zip(spec, segs):
        if wdt == _BITS:
            n = int(np.prod(shape)) if shape else 1
            bits = (seg[:, None] >> jnp.arange(8, dtype=jnp.uint8)) \
                & jnp.uint8(1)
            out[k] = bits.reshape(-1)[:n].astype(jnp.bool_).reshape(shape)
            continue
        dtype = np.dtype(wdt)
        if dtype == np.uint8:
            arr = seg.reshape(shape)
        elif dtype == np.bool_:
            arr = seg.reshape(shape).astype(jnp.bool_)
        elif dtype.itemsize == 8:
            halves = jax.lax.bitcast_convert_type(
                seg.reshape(tuple(shape) + (2, 4)), jnp.uint32)
            lo = halves[..., 0].astype(jnp.uint64)
            hi = halves[..., 1].astype(jnp.uint64)
            arr = (lo | (hi << jnp.uint64(32))).astype(jnp.dtype(dt))
        else:
            it = dtype.itemsize
            arr = jax.lax.bitcast_convert_type(
                seg.reshape(tuple(shape) + (it,)), jnp.dtype(dtype))
        if dtype != np.dtype(dt) and arr.dtype != np.dtype(dt):
            arr = arr.astype(jnp.dtype(dt))     # re-widen narrowed wires
        out[k] = arr
    return out


def _device_pack(outs: dict, skip=(), lo32: dict | None = None):
    """Traced: dict of packable arrays -> (u8 buffer, spec). Keys in
    `skip` ride the varlen payload but stay visible here so wire
    narrowing still sees its siblings; keys in `lo32` ship only their low
    u32 word here (high words ride the varlen payload)."""
    lo32 = lo32 or {}
    segs = []
    spec = []
    off = 0
    for k in sorted(outs):
        if k in skip:
            continue
        v = jnp.asarray(outs[k])
        orig_dt = np.dtype(v.dtype).str
        if k in lo32:
            wd = _LO32[orig_dt]
            u = jax.lax.bitcast_convert_type(lo32[k], jnp.uint8).reshape(-1)
        else:
            wd = _wire_dtype(k, np.dtype(v.dtype), outs)
            if wd == _BITS:
                u = _bitpack_dev(v)
            elif wd.startswith("pb"):
                b_in = int(wd[2:])
                bm = jnp.zeros(b_in, jnp.bool_).at[v].set(True, mode="drop")
                u = _bitpack_dev(bm)
            else:
                if np.dtype(wd) != np.dtype(v.dtype):
                    v = v.astype(jnp.dtype(wd))     # narrowed wire dtype
                if v.dtype == jnp.uint8:
                    u = v.reshape(-1)
                elif v.dtype == jnp.bool_:
                    u = v.astype(jnp.uint8).reshape(-1)
                elif v.dtype.itemsize == 8:
                    w = v.astype(jnp.uint64) if v.dtype == jnp.int64 else v
                    lo = (w & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32)
                    hi = (w >> jnp.uint64(32)).astype(jnp.uint32)
                    halves = jnp.stack([lo, hi], axis=-1)
                    u = jax.lax.bitcast_convert_type(
                        halves, jnp.uint8).reshape(-1)
                else:
                    u = jax.lax.bitcast_convert_type(
                        v, jnp.uint8).reshape(-1)
        nb = int(u.shape[0])
        pad = _pad(nb) - nb
        if pad:
            u = jnp.pad(u, (0, pad))
        segs.append(u)
        spec.append((k, tuple(v.shape), orig_dt, off, nb, wd))
        off += _pad(nb)
    buf = jnp.concatenate(segs) if segs else jnp.zeros(0, jnp.uint8)
    return buf, tuple(spec)


def _varlen_str_keys(outs: dict) -> tuple:
    """Output keys eligible for the varlen string wire: 2-D u8 '#bytes'
    matrices with an int '#len' sibling (the StrLeaf layout). Sorted so
    the device payload order and the host re-derivation agree byte for
    byte."""
    ks = []
    for k in sorted(outs):
        if not k.endswith("#bytes"):
            continue
        v = outs[k]
        lk = k[:-6] + "#len"
        if getattr(v, "ndim", 0) == 2 and np.dtype(v.dtype) == np.uint8 \
                and lk in outs \
                and np.dtype(outs[lk].dtype).kind in "iu":
            ks.append(k)
    return tuple(ks)


def _varlen_i64_keys(outs: dict, str_keys: tuple) -> tuple:
    """1-D 64-bit leaves whose high words ride the varlen payload (the
    low word ships fixed as u32). On data like zillow the values fit 32
    bits almost everywhere, so this halves every i64 column."""
    skip = set(str_keys)
    return tuple(k for k in sorted(outs)
                 if k not in skip
                 and getattr(outs[k], "ndim", 0) == 1
                 and np.dtype(outs[k].dtype) in (np.dtype(np.int64),
                                                 np.dtype(np.uint64)))


def _live_masks(args, outs):
    """(live_slot, live_input) bool masks — rows the host merge can ever
    read from the fast-path outputs (rowvalid & keep & err==0, mapped
    through '#rowidx' for compacted outputs). Dead rows' varlen bytes are
    suppressed: padding/filtered/errored slots would otherwise ship
    garbage content over the D2H link. None when the outputs don't
    carry the stage lattice (non-stage uses of the packer)."""
    keep = outs.get("#keep")
    err = outs.get("#err")
    if keep is None or err is None or getattr(keep, "ndim", 0) != 1 \
            or getattr(err, "shape", None) != keep.shape:
        return None, None
    live = keep & (err == 0)
    rv = args.get("#rowvalid") if isinstance(args, dict) else None
    if rv is not None and getattr(rv, "shape", None) == live.shape:
        live = live & rv
    rowidx = outs.get("#rowidx")
    if rowidx is None or getattr(rowidx, "ndim", 0) != 1:
        return live, live
    b_in = live.shape[0]
    ri = jnp.clip(rowidx, 0, b_in - 1)
    live_slot = live[ri] & (rowidx < b_in)
    return live_slot, live


def _bitpack_dev(v):
    """Traced: 1-D bool -> little-endian bitpacked u8[ceil(n/8)]."""
    n = int(v.shape[0])
    nb8 = (n + 7) // 8
    b = v.astype(jnp.int32)
    if nb8 * 8 != n:
        b = jnp.pad(b, (0, nb8 * 8 - n))
    return (b.reshape(nb8, 8) << jnp.arange(8, dtype=jnp.int32)) \
        .sum(axis=1).astype(jnp.uint8)


def _u32_bytes(v):
    return jax.lax.bitcast_convert_type(v.astype(jnp.uint32), jnp.uint8)


def _device_pack_varlen(entries: list):
    """Traced: scatter every varlen entry's actual row bytes into ONE
    contiguous payload, handed back as equal chunks (`_payload_chunking`).
    entries: (kind, key, mat u8 [B, w], lens i32 [B], dt_str). Capacity
    is the static worst case so the executable is shape-stable; the host
    fetches only the chunks that hold payload[:total] after re-deriving
    the per-row lengths from the fixed buffer."""
    lens = [e[3].astype(jnp.int64) for e in entries]
    all_lens = jnp.concatenate(lens)
    offs = jnp.cumsum(all_lens) - all_lens          # exclusive cumsum
    nch, chunk = _payload_chunking(
        sum(int(e[2].shape[0] * e[2].shape[1]) for e in entries))
    cap = nch * chunk
    payload = jnp.zeros(cap, jnp.uint8)
    vspec = []
    row0 = 0
    for (kind, k, mat, ln, dt), ln64 in zip(entries, lens):
        b, w = mat.shape
        o = offs[row0:row0 + b]
        idx = o[:, None] + jnp.arange(w, dtype=o.dtype)[None, :]
        m = jnp.arange(w, dtype=jnp.int32)[None, :] < \
            ln.astype(jnp.int32)[:, None]
        idx = jnp.where(m, idx, cap)                # OOB -> dropped
        payload = payload.at[idx.reshape(-1)].set(
            mat.reshape(-1), mode="drop")
        vspec.append((kind, k, (b, w), dt))
        row0 += b
    return tuple(payload.reshape(nch, chunk)), tuple(vspec)


def _build_varlen(args, outs, pack_outs):
    """Assemble the varlen plan inside the trace. Mutates pack_outs
    (masked lens, synthetic '#need' bitmaps) and returns
    (entries, skip_keys, lo32)."""
    entries = []
    skip = set()
    lo32 = {}
    live_slot, live_in = _live_masks(args, pack_outs)
    str_keys = _varlen_str_keys(pack_outs)
    if live_slot is not None:
        # ship the liveness mask (bitpacked) so the host derives the same
        # layout lengths WITHOUT altering the '#len' leaves — dead slots
        # (padding/filtered/errored; unread by every consumer, the merge
        # gathers only rowvalid & keep & err==0 rows) contribute zero
        # payload bytes instead of garbage content
        pack_outs["#live"] = live_slot
    # -- str leaves: actual bytes instead of padded [B, W] matrices ------
    for bk in str_keys:
        lk = bk[:-6] + "#len"
        mat = jnp.asarray(pack_outs[bk])
        b, w = mat.shape
        ln = jnp.clip(jnp.asarray(pack_outs[lk]).astype(jnp.int32)
                      .reshape(-1), 0, w)
        if live_slot is not None and live_slot.shape == ln.shape:
            ln = ln * live_slot
        entries.append(("str", bk, mat, ln, "|u1"))
        skip.add(bk)
    # -- 64-bit leaves: low word u32, high words varlen ------------------
    for k in _varlen_i64_keys(pack_outs, tuple(skip)):
        v = jnp.asarray(pack_outs[k])
        dt = np.dtype(v.dtype)
        w64 = v.astype(jnp.uint64)
        lo = (w64 & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32)
        hi = (w64 >> jnp.uint64(32)).astype(jnp.uint32)
        sext = ((lo.astype(jnp.int32) >> 31).astype(jnp.uint32)
                if dt == np.dtype(np.int64) else jnp.uint32(0))
        need = hi != sext
        if live_slot is not None and live_slot.shape == need.shape:
            # liveness known: the low words ride the payload too, so dead
            # slots ship zero bytes instead of 4 garbage ones
            need = need & live_slot
            entries.append(("lo32v", k, _u32_bytes(lo),
                            live_slot.astype(jnp.int32) * 4, dt.str))
            skip.add(k)
        else:
            lo32[k] = lo                    # low word fixed-buffer u32
        pack_outs[k + "#need"] = need       # 1-D bool -> bitpacked wire
        entries.append(("hi32", k, _u32_bytes(hi),
                        need.astype(jnp.int32) * 4, dt.str))
    # -- '#err': zero-dominated lattice -> sparse nonzero codes ----------
    err = pack_outs.get("#err")
    if err is not None and getattr(err, "ndim", 0) == 1 \
            and np.dtype(err.dtype) == np.dtype(np.int32):
        ev = jnp.asarray(err)
        need = ev != 0
        rv = args.get("#rowvalid") if isinstance(args, dict) else None
        if rv is not None and getattr(rv, "shape", None) == need.shape:
            need = need & rv                # padding rows' codes are noise
        pack_outs["#err#need"] = need
        entries.append(("sparse32", "#err", _u32_bytes(ev),
                        need.astype(jnp.int32) * 4, "<i4"))
        skip.add("#err")
    return entries, tuple(sorted(skip)), lo32


class PackedOuts:
    """Async handle for a packed stage result: one fixed-layout device
    buffer + layout, an optional varlen payload (str leaves as actual
    bytes; `vbuf`: its equal chunks, each a buffer of its own), plus any
    per-leaf arrays whose dtype can't ride the buffer (f64)."""

    __slots__ = ("buf", "spec", "extras", "vbuf", "vspec")

    def __init__(self, buf, spec, extras=None, vbuf=None, vspec=()):
        self.buf = buf
        self.spec = spec
        self.extras = extras or {}
        self.vbuf = tuple(vbuf or ())
        self.vspec = tuple(vspec or ())

    def to_host(self) -> dict:
        import os
        import time

        from . import tracing as TR
        from . import xferstats

        t0 = time.perf_counter()
        with TR.span("d2h:packed-fetch", "xfer") as _sp:
            host = np.asarray(jax.device_get(self.buf))
            out = _unpack_host(host, self.spec)
            fetched = host.nbytes
            if self.vspec:
                with TR.span("d2h:varlen-unpack", "xfer") as _vsp:
                    vb, native = self._unpack_varlen(out)
                    _vsp.set("bytes", vb)
                    _vsp.set("native", int(native))
                    _vsp.set("entries", len(self.vspec))
                fetched += vb
            if self.extras:
                ex = jax.device_get(self.extras)
                fetched += sum(np.asarray(v).nbytes for v in ex.values())
                out.update(ex)
            _sp.set("bytes", fetched)
        xferstats.note_d2h(fetched, tag="packed_fetch")
        if os.environ.get("TUPLEX_PACK_DEBUG"):
            import sys

            print(f"[pack] d2h {fetched >> 20}MB ({len(self.vspec)} varlen"
                  f"+{len(self.extras)}x) "
                  f"{time.perf_counter() - t0:.3f}s", file=sys.stderr,
                  flush=True)
        return out

    def _unpack_varlen(self, out: dict) -> tuple:
        """Fetch the chunks that hold payload[:total] (whole buffers: no
        executable joins the queue behind the dispatches in flight) and
        rebuild every varlen entry in place — str byte matrices, i64 high
        words, sparse '#err' codes. The per-row lengths re-derive
        deterministically from the fixed buffer (shipped lens / '#need'
        bitmaps), so no offsets travel. Returns (bytes fetched, whether
        the native call built the matrices)."""
        live = out.pop("#live", None)
        live4 = None if live is None else np.asarray(live, dtype=np.int64) * 4
        lens = []
        total = 0
        for kind, k, (b, w), dt in self.vspec:
            if kind == "str":
                ln = np.clip(np.asarray(out[k[:-6] + "#len"],
                                        dtype=np.int64).reshape(-1), 0, w)
                if live is not None and live.shape == ln.shape:
                    ln = ln * live
            elif kind == "lo32v":
                ln = live4
            else:
                ln = np.asarray(out[k + "#need"],
                                dtype=np.int64).reshape(-1) * 4
            lens.append(ln)
            total += int(ln.sum())
        chunk = int(self.vbuf[0].shape[0])
        parts = jax.device_get(list(self.vbuf[:-(-total // chunk)]))
        payload = np.concatenate(parts) if parts else np.zeros(0, np.uint8)
        mats, native = _varlen_matrices(
            payload, lens, [w for _kind, _k, (_b, w), _dt in self.vspec])
        for (kind, k, _shape, dt), mat in zip(self.vspec, mats):
            if kind == "str":
                out[k] = mat
                continue
            words = np.ascontiguousarray(
                np.ascontiguousarray(mat).view("<u4")[:, 0])
            if kind == "lo32v":
                # dead rows carried no bytes -> lo 0 -> value 0 (unread)
                out[k] = (words.view(np.int32).astype(np.int64)
                          if np.dtype(dt) == np.dtype(np.int64)
                          else words.astype(np.uint64))
                continue
            need = np.asarray(out.pop(k + "#need"), dtype=np.bool_)
            if kind == "sparse32":
                out[k] = np.where(need, words.view("<i4"),
                                  0).astype(np.dtype(dt), copy=False)
            elif need.any():
                # hi32: patch the rows whose high word isn't the low
                # word's sign/zero extension
                base = np.asarray(out[k]).view(np.uint64)
                lo = base & np.uint64(0xFFFFFFFF)
                full = lo | (words.astype(np.uint64) << np.uint64(32))
                out[k] = np.where(need, full,
                                  base).view(np.dtype(dt))
        return payload.nbytes, native


def _varlen_matrices(payload: np.ndarray, lens: list, widths: list):
    """The varlen payload's entries, which lie back to back in it, each as
    its zero-padded [n, max(w, 1)] uint8 matrix: (matrices, native).
    `lens`: an int64 array an entry, every value in [0, w]. One native call
    a partition (`unpack_varlen`: a memcpy a row, the interpreter lock let
    go once) where the module is loaded; `columns.varlen_to_matrix` an
    entry, its pure-numpy twin, where it is not."""
    from ..native import get as _native_get

    nat = _native_get()
    if nat is not None and hasattr(nat, "unpack_varlen"):
        lens = [np.ascontiguousarray(ln, dtype=np.int64) for ln in lens]
        mats = [np.empty((len(ln), max(w, 1)), np.uint8)
                for ln, w in zip(lens, widths)]
        nat.unpack_varlen(np.ascontiguousarray(payload, dtype=np.uint8),
                          list(zip(lens, widths, mats)))
        return mats, True
    from .columns import varlen_to_matrix

    mats = []
    off = 0
    for ln, w in zip(lens, widths):
        offs = off + np.concatenate(
            [[0], np.cumsum(ln, dtype=np.int64)])[:-1]
        mats.append(varlen_to_matrix(payload, offs, ln, w))
        off += int(ln.sum())
    return mats, False


class PackedStageFn:
    """Drop-in for jit(raw_fn): __call__(arrays_dict) -> PackedOuts.

    One compiled executable per input layout (same granularity as jit's
    shape retrace). The output layout is recorded as a trace side effect.

    With the varlen wire (runtime/jaxcfg.varlen_wire_enabled) str '#bytes'
    outputs leave the fixed buffer and ship as one contiguous payload of
    actual row bytes — on zillow that's the difference between ~170 B/row
    of padding and ~30 B of content on the D2H link."""

    def __init__(self, raw_fn, donate: bool, tag: str = "", n_ops: int = 0,
                 deadline=None):
        from .jaxcfg import varlen_wire_enabled

        self._raw = raw_fn
        self._donate = donate
        self._varlen = varlen_wire_enabled()
        self._fns: dict = {}
        self._tag = tag          # compile-seconds attribution (stage key)
        self._n_ops = n_ops      # graphlint's vetting, compile:* spans
        self._deadline = deadline   # compile deadline (CompileTimeout)
        self._last_fn = None     # the per-layout fn the last call launched

    @property
    def last_module(self):
        """HLO module name of the executable the last call launched
        (`dispatch:launch.module`)."""
        return getattr(self._last_fn, "last_module", None)

    def _make_entry(self, spec, ekey):
        """Build (and cache) the per-layout compiled entry: the traced
        closure that unpacks `spec`, runs the stage, and re-packs —
        shared verbatim by dispatch (__call__) and the AOT prewarm
        (``warm``), so both produce the SAME jaxpr and therefore the same
        content address in exec/compilequeue."""
        cell: dict = {}

        def traced(buf, extras):
            args = _device_unpack(buf, spec)
            args.update(extras)
            outs = self._raw(args)
            pack_outs = {k: v for k, v in outs.items()
                         if _packable(jnp.asarray(v).dtype)}
            extra_outs = {k: v for k, v in outs.items()
                          if k not in pack_outs}
            entries, vskip, lo32 = (
                _build_varlen(args, outs, pack_outs)
                if self._varlen else ([], (), {}))
            obuf, ospec = _device_pack(pack_outs, skip=vskip,
                                       lo32=lo32)
            vbuf, vspec = (_device_pack_varlen(entries) if entries
                           else ((), ()))
            cell["ospec"] = ospec
            cell["vspec"] = vspec
            return obuf, vbuf, extra_outs

        # the wire closure carries the stage's own key: `jit_tpx_pack_<key8>`
        # beside the unpacked `jit_tpx_stage_<key8>` of the same stage
        from . import tracing as TR

        TR.name_fn(traced, "pack", TR.fn_key8(self._raw, self._tag))
        # content-addressed AOT route (exec/compilequeue): the trace —
        # which records ospec/vspec into `cell` as a side effect — runs
        # on every path (fingerprinting always traces); only the XLA
        # compile is skipped on a fingerprint or disk-artifact hit
        from ..exec.compilequeue import aot_jit

        fn = aot_jit(traced, donate=self._donate, salt="pack",
                     tag=self._tag, n_ops=self._n_ops,
                     deadline=self._deadline)
        entry = (fn, cell, traced)
        self._fns[(spec, ekey)] = entry
        return entry

    def traced_for(self, avals: dict):
        """(traced closure, wire-buffer aval, extras avals) of the packed
        executable for a dict of leaf avals — the layout derives from the
        avals alone. (None, None, None) when nothing is packable. Shared
        by ``warm`` and the chip-compile test, so both lower exactly what
        dispatch would."""
        entry, buf_aval, ex_avals = self._entry_for(avals)
        return entry and entry[2], buf_aval, ex_avals

    def _entry_for(self, avals: dict):
        spec, total = _host_spec(avals, check_values=False)
        if not spec:
            return None, None, None
        extras = {k: v for k, v in avals.items()
                  if not _packable(np.dtype(v.dtype))}
        ekey = tuple(sorted((k, tuple(v.shape), np.dtype(v.dtype).str)
                            for k, v in extras.items()))
        entry = self._fns.get((spec, ekey))
        if entry is None:
            entry = self._make_entry(spec, ekey)
        buf_aval = jax.ShapeDtypeStruct((total,), np.uint8)
        ex_avals = {k: jax.ShapeDtypeStruct(tuple(v.shape),
                                            np.dtype(v.dtype))
                    for k, v in extras.items()}
        return entry, buf_aval, ex_avals

    def warm(self, avals: dict):
        """Ahead-of-time compile against PREDICTED avals (the precompile
        driver's chained shape walk): derive the wire-buffer layout from
        the leaf avals alone and queue the packed executable's compile on
        the pool, so a varlen-wire stage finds its executable already
        built (or on disk) at first dispatch instead of compiling inline.
        Returns the pool Future, or None when the layout has no packable
        leaves. Speculative by construction: a value-dependent '#len'
        narrowing miss only wastes one background compile."""
        entry, buf_aval, ex_avals = self._entry_for(avals)
        # the per-layout AotJit dispatch calls: one set of compile
        # arguments for both sides (a bare jit, TUPLEX_AOT_JIT=0, has none)
        warm = getattr(entry and entry[0], "warm", None)
        return warm(buf_aval, ex_avals) if warm is not None else None

    def note_async_defect(self) -> bool:
        """Forward the async deserialize-defect verdict (see
        AotJit.note_async_defect) to every per-spec AOT route this
        packed fn built; True when any entry was pinned to the plain
        in-process jit."""
        hit = False
        for fn, _cell, _traced in self._fns.values():
            noted = getattr(fn, "note_async_defect", None)
            if noted is not None and noted():
                hit = True
        return hit

    def __call__(self, arrays: dict):
        spec, total = _host_spec(arrays)
        extras_in = {k: v for k, v in arrays.items()
                     if not _packable(v.dtype)}
        ekey = tuple(sorted((k, tuple(v.shape), v.dtype.str)
                            for k, v in extras_in.items()))
        entry = self._fns.get((spec, ekey))
        if entry is None:
            entry = self._make_entry(spec, ekey)
        fn, cell = entry[0], entry[1]
        self._last_fn = fn
        import os

        if os.environ.get("TUPLEX_PACK_DEBUG"):
            import sys
            import time

            t0 = time.perf_counter()
            buf = _pack_host(arrays, spec, total)
            t1 = time.perf_counter()
            dbuf, vbuf, extra_outs = fn(jax.device_put(buf), extras_in)
            jax.block_until_ready(dbuf)
            print(f"[pack] host-pack {total >> 20}MB {t1 - t0:.3f}s; "
                  f"h2d+exec {time.perf_counter() - t1:.3f}s",
                  file=sys.stderr, flush=True)
            return PackedOuts(dbuf, cell["ospec"], extra_outs,
                              vbuf, cell["vspec"])
        from . import tracing as TR
        from . import xferstats

        h2d_bytes = 0
        with TR.span("h2d:packed-upload", "xfer") as _sp:
            buf = _pack_host(arrays, spec, total)
            h2d_bytes = buf.nbytes + sum(np.asarray(v).nbytes
                                         for v in extras_in.values())
            _sp.set("bytes", h2d_bytes)
            # explicit placement rather than letting the jit call
            # transfer its numpy argument (faster where it was measured;
            # not measured on this machine)
            dev = jax.device_put(buf)
        xferstats.note_h2d(h2d_bytes, tag="packed_dispatch")
        dbuf, vbuf, extra_outs = fn(dev, extras_in)
        return PackedOuts(dbuf, cell["ospec"], extra_outs,
                          vbuf, cell["vspec"])
